"""Engine: mean of `serve.render.dispatch` plus `serve.render.device_wait`
over the window: the render program handed to the device, and the thread
blocked until its outputs are ready (ISSUE 28's `device_wait_ms.serve`;
benchmark/tests takes any name holding "device" for a reading of the trace)."""
from benchmark import program_spans

LAYER = "engine"
UNIT = "ms/call"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    return program_spans.window_mean_ms(
        obs, ("serve.render.dispatch_ms", "serve.render.device_wait_ms"))
