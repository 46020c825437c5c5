"""Host feed: share of the traced window in which the chip idled while the
train loop waited on the stager (`data.stage.starved`) and the stager
waited on the host iterator for a batch (`data.stage.host_wait`: an
epoch's open, a batch still being assembled).
Split instant by instant in benchmark/idle_spans.py."""
from benchmark import idle_spans

LAYER = "host feed"
UNIT = "%"
SOURCE = "program_span"
MOVES = "train_images_per_s"


def read(obs):
    return idle_spans.share(obs, "train", "host_batch")
