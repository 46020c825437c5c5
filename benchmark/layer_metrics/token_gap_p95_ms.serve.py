"""Token scheduler: 95th percentile of the time between two consecutive
answer tokens of one request: the steps that carried a 2,048-token chunk
against a long prefix."""
LAYER = "lm scheduler"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "serve_views_per_s"


def read(obs):
    return obs["counters"].get("token_gap_p95_ms")
