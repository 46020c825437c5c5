"""Train step: time in which an operation ran on the device inside one
execution of the step program, from the trace."""
from benchmark import trace_reduce

LAYER = "train step"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_images_per_s"


def read(obs):
    if obs["trace"] is None:
        return None
    secs, runs = trace_reduce.per_run(
        obs["trace"], obs["counters"].get("step_program", "train_step"))
    return None if not runs else secs * 1e3
