"""Train step: model FLOP/s utilization of the whole step: the operations the
forward and backward passes of one step require (benchmark/roofline_lm.py:
6 * tokens * (passes * L * P_layer + passes * P_head) + attention; recomputed
operations do not count) over the step's device time times the chip's
bfloat16 peak."""
from benchmark import roofline_lm, trace_reduce

LAYER = "train step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_images_per_s"


def read(obs):
    if obs["trace"] is None or obs["shapes"].get("kind") != "lm_train":
        return None
    secs, runs = trace_reduce.per_run(
        obs["trace"], obs["counters"].get("step_program", "train_step"))
    if not runs or not secs:
        return None
    peak = obs["peaks"]["peak_tflops_bf16"] * 1e12
    return 100.0 * roofline_lm.model_flops_per_step(obs["shapes"]) / (
        secs * peak)
