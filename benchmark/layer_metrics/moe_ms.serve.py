"""Token server, a step's expert layers on the device: router + the held
experts' grouped products (sort, gather, three products, the way back) +
the shared expert, ms a step over the traced steps."""
from benchmark import lm_serve_spans

LAYER = "lm step"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    return lm_serve_spans.layer_ms_per_step(obs, lm_serve_spans.MOE)
