"""Kernels: summed device time of the Pallas custom calls (warp and
composite, forward and backward, every loss scale) in one train step."""
from benchmark import trace_reduce

LAYER = "kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_images_per_s"


def read(obs):
    if obs["trace"] is None:
        return None
    secs, runs = trace_reduce.per_run(
        obs["trace"], obs["counters"].get("step_program", "train_step"),
        trace_reduce.is_pallas_call)
    return None if not runs or not secs else secs * 1e3
