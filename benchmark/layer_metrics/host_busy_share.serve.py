"""Engine: share of the window the dispatching thread was busy on the host
inside a flush, the device necessarily idle: `serve.batcher.flush` less its
`serve.render.dispatch` and `serve.render.device_wait` (gather, pad and
place, readback, deliver, and the self time of the flush and of `_call`)."""
from benchmark import program_spans

LAYER = "engine"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    return program_spans.window_share(
        obs, ("serve.batcher.flush_ms",),
        minus=("serve.render.dispatch_ms", "serve.render.device_wait_ms"))
