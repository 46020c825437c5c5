"""Device: share of the traced window in which the chip idled and no span
of the loop's thread names why: the loop's own sync and epoch change,
Python between calls, a starved instant with no stager span open.
Split instant by instant in benchmark/idle_spans.py."""
from benchmark import idle_spans

LAYER = "device"
UNIT = "%"
SOURCE = "program_span"
MOVES = "train_images_per_s"


def read(obs):
    return idle_spans.share(obs, "train", "unnamed")
