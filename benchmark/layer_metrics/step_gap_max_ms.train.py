"""Train step: the longest time between the starts of two consecutive
`train.step.dispatch` spans in the window: the loop's sync at log cadence in
a quiet run, a stall of the device or of the host in a run that reads low."""
from benchmark import program_spans

LAYER = "train step"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_images_per_s"


def read(obs):
    return program_spans.step_gap_max_ms(obs)
