"""Engine: mean of `serve.render_fetch` over the window: slicing the P views
out of the bucket and copying them to the host."""
from benchmark import program_spans

LAYER = "engine"
UNIT = "ms/call"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    return program_spans.window_mean_ms(
        obs, ("serve.render_fetch_ms",))
