"""Engine: share of the traced window in which the chip idled while the
batcher's thread was in `serve.render.dispatch` or
`serve.render.device_wait` (the render program's launch).
Split instant by instant in benchmark/idle_spans.py."""
from benchmark import idle_spans

LAYER = "engine"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    return idle_spans.share(obs, "serve", "launch")
