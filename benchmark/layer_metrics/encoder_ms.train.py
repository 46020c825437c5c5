"""Train step: self time of the step program's operations that the program's
map (`mine_tpu.telemetry.programs`) puts in the layer `encoder`."""
from benchmark import program_spans

LAYER = "train step"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_images_per_s"


def read(obs):
    return program_spans.layer_ms(obs, "encoder")
