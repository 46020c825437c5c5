"""Device: share of the traced window in which the chip idled and the
batcher's thread was in no program span (between its waits and flushes).
Split instant by instant in benchmark/idle_spans.py."""
from benchmark import idle_spans

LAYER = "device"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    return idle_spans.share(obs, "serve", "unnamed")
