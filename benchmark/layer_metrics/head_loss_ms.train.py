"""Train step of the looped language model: self time of the step program's
operations that the program's map (`mine_tpu.telemetry.programs`) puts in
the layer `head_loss`: the head's, the chunked cross entropy's, the final norm's and the exit gate's, forward, rematerialised forward and backward, every
pass."""
from benchmark import program_spans

LAYER = "train step"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_images_per_s"


def read(obs):
    if obs["shapes"].get("kind") != "lm_train":
        return None
    return program_spans.layer_ms(obs, "head_loss")
