"""Host feed: share of the traced window in which the chip idled while the
train loop waited on the stager (`data.stage.starved`) and the stager was
copying a batch to the device (`data.stage.h2d` open on its thread).
Split instant by instant in benchmark/idle_spans.py."""
from benchmark import idle_spans

LAYER = "host feed"
UNIT = "%"
SOURCE = "program_span"
MOVES = "train_images_per_s"


def read(obs):
    return idle_spans.share(obs, "train", "h2d")
