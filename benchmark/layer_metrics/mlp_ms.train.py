"""Train step of the looped language model: self time of the step program's
operations that the program's map (`mine_tpu.telemetry.programs`) puts in
the layer `mlp`: the SwiGLU sub-layer's (norms, the three matmuls), forward, rematerialised forward and backward, every
pass."""
from benchmark import program_spans

LAYER = "train step"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_images_per_s"


def read(obs):
    if obs["shapes"].get("kind") != "lm_train":
        return None
    return program_spans.layer_ms(obs, "mlp")
