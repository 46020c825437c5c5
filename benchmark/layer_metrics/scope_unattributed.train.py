"""Train step: self time of the step program's operations to which the
program's map gives no layer, as a share of the step's device time."""
from benchmark import program_spans, trace_reduce

LAYER = "train step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_images_per_s"


def read(obs):
    rest = program_spans.layer_ms(obs, None)
    if rest is None:
        return None
    secs, runs = trace_reduce.per_run(
        obs["trace"], obs["counters"].get("step_program", "train_step"))
    return None if not runs or not secs else 100.0 * rest / (secs * 1e3)
