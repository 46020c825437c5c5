"""Train step: mean of `train.step.dispatch`, the training thread inside
`SynthesisTrainer.train_step` (it returns once the step is enqueued; it grows
when the host is short of cores or the device's queue is full)."""
from benchmark import program_spans

LAYER = "train step"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "train_images_per_s"


def read(obs):
    return program_spans.ring_ms_per(obs, "train.step.dispatch", "step")
