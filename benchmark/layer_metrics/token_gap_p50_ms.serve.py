"""Token scheduler: median time between two consecutive answer tokens of one
request at its worker, over the window: a step's length as a reader of the
answer feels it."""
LAYER = "lm scheduler"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "serve_views_per_s"


def read(obs):
    return obs["counters"].get("token_gap_p50_ms")
