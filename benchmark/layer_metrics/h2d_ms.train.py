"""Host feed: mean of `data.stage.h2d`, one batch put on the device and
waited for, on the stager's thread."""
from benchmark import program_spans

LAYER = "host feed"
UNIT = "ms/batch"
SOURCE = "program_span"
MOVES = "train_images_per_s"


def read(obs):
    return program_spans.ring_ms_per(obs, "data.stage.h2d", "span")
