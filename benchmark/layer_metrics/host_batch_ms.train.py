"""Host feed: mean of `data.assemble.batch`, one host batch built by an
assembler thread (summed over the threads, over the batches)."""
from benchmark import program_spans

LAYER = "host feed"
UNIT = "ms/batch"
SOURCE = "program_span"
MOVES = "train_images_per_s"


def read(obs):
    return program_spans.ring_ms_per(obs, "data.assemble.batch", "span")
