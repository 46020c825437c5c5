"""Engine: share of the traced window in which the chip idled while the
batcher's thread did host work inside a flush: gather, pad and place,
readback, deliver, and the own time of the flush, the call and the
device span (everything but `serve.render.dispatch` / `.device_wait`).
Split instant by instant in benchmark/idle_spans.py."""
from benchmark import idle_spans

LAYER = "engine"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    return idle_spans.share(obs, "serve", "host")
