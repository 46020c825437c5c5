"""Host feed: time the training thread waited in `data.stage.starved` (the
stager's queue empty), per step: the inside twin of `feed_wait_ms.train`."""
from benchmark import program_spans

LAYER = "host feed"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "train_images_per_s"


def read(obs):
    return program_spans.ring_ms_per(obs, "data.stage.starved", "step")
