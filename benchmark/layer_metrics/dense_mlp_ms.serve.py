"""Token server: layer 0's dense SwiGLU (18,432 wide) on the device, ms a
step over the traced steps."""
from benchmark import lm_serve_spans

LAYER = "lm step"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    return lm_serve_spans.layer_ms_per_step(obs, ("dense_mlp",))
