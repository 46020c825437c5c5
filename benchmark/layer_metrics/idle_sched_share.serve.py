"""Scheduler: share of the traced window in which the chip idled while the
batcher's thread waited for requests (`serve.batcher.idle`) or for
co-riders (`serve.batcher.linger`): capacity the offered rate leaves.
Split instant by instant in benchmark/idle_spans.py."""
from benchmark import idle_spans

LAYER = "scheduler"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    return idle_spans.share(obs, "serve", "sched")
