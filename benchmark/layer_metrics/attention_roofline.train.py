"""Kernels: the least time the chip could take for one step's causal
attention (benchmark/roofline_lm.py, from shapes: every layer application
forward and backward, the larger of operations over peak and bytes over
bandwidth; compute binds at 4,096 tokens) over the time the attention
kernels' custom calls took in the trace. The rematerialised forward is
recomputation: its time counts, the floor does not hold it."""
from benchmark import roofline_lm, trace_reduce

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_images_per_s"
KERNEL = "flash_attention"


def _is_attention(op):
    return (trace_reduce.is_pallas_call(op)
            and KERNEL in trace_reduce.instruction(op["name"])[0])


def read(obs):
    if obs["trace"] is None or obs["shapes"].get("kind") != "lm_train":
        return None
    secs, runs = trace_reduce.per_run(
        obs["trace"], obs["counters"].get("step_program", "train_step"),
        _is_attention)
    if not runs or not secs:
        return None
    return 100.0 * roofline_lm.attention_step_floor_s(
        obs["shapes"], obs["peaks"]) / secs
