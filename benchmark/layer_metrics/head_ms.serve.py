"""Token server: the final norm, the head over the sampled rows and the
greedy argmax on the device, ms a step over the traced steps."""
from benchmark import lm_serve_spans

LAYER = "lm step"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    return lm_serve_spans.layer_ms_per_step(obs, ("head",))
