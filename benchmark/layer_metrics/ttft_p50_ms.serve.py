"""Token scheduler: median time from a request's submission to its first
answer token at the worker, over the first tokens delivered in the window
(queueing behind older prompts, the prompt's own chunks, one readback)."""
LAYER = "lm scheduler"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "serve_views_per_s"


def read(obs):
    return obs["counters"].get("ttft_p50_ms")
