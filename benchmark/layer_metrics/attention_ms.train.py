"""Train step of the looped language model: self time of the step program's
operations that the program's map (`mine_tpu.telemetry.programs`) puts in
the layer `attention`: the attention sub-layer's (norms, projections, RoPE, the attention kernels), forward, rematerialised forward and backward, every
pass."""
from benchmark import program_spans

LAYER = "train step"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_images_per_s"


def read(obs):
    if obs["shapes"].get("kind") != "lm_train":
        return None
    return program_spans.layer_ms(obs, "attention")
