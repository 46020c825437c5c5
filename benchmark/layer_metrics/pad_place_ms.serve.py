"""Engine: mean of `serve.render.pad_place` over the window: stacking the
entries of a call, padding to the bucket, placing the arguments."""
from benchmark import program_spans

LAYER = "engine"
UNIT = "ms/call"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    return program_spans.window_mean_ms(
        obs, ("serve.render.pad_place_ms",))
