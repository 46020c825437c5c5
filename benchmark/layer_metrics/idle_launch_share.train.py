"""Train step: share of the traced window in which the chip idled while the
train loop was inside `train.step.dispatch` (the step's launch).
Split instant by instant in benchmark/idle_spans.py."""
from benchmark import idle_spans

LAYER = "train step"
UNIT = "%"
SOURCE = "program_span"
MOVES = "train_images_per_s"


def read(obs):
    return idle_spans.share(obs, "train", "launch")
