"""Token server: time in which an operation ran on the device inside one
execution of a step program, mean over the traced steps (the steps differ:
a decode-only step and a 2,048-token chunk are both steps)."""
from benchmark import lm_serve_spans

LAYER = "lm step"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    found = lm_serve_spans.device_by_layer(obs)
    return None if not found else 1e3 * found["busy_s"] / found["executions"]
