"""Scheduler: mean time a request waited in the batcher's queue, from the
program's `serve.batcher.queue_wait_ms` histogram over the window."""
from benchmark import harness

LAYER = "scheduler"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    return harness.registry_window_mean(obs["registry"],
                                        "serve.batcher.queue_wait_ms")
