"""Engine: mean of the program's `serve.render_call_ms` histogram over the
window: one `RenderEngine._call`, stack / pad / place -> dispatch -> readback
of the views to the host."""
from benchmark import harness

LAYER = "engine"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    return harness.registry_window_mean(obs["registry"],
                                        "serve.render_call_ms")
