"""Host feed: mean of `data.iterator.open`: an epoch's `batch_iterator`
started until its first batch is ready (what an epoch's edge costs)."""
from benchmark import program_spans

LAYER = "host feed"
UNIT = "ms/epoch"
SOURCE = "program_span"
MOVES = "train_images_per_s"


def read(obs):
    return program_spans.ring_ms_per(obs, "data.iterator.open", "span")
