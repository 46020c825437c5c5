"""Token scheduler: share of the window's step tokens that were prompt-chunk
tokens (the rest are decode tokens), from the `serve.lm.step` spans."""
from benchmark import lm_serve_spans

LAYER = "lm scheduler"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    steps = lm_serve_spans.window_steps(obs)
    if not steps:
        return None
    tokens = sum(s["tokens"] for s in steps)
    return 100.0 * sum(s["prefill"] for s in steps) / tokens if tokens else None
