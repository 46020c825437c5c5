"""Scheduler: share of the window that none of the batcher thread's three
top-level spans covers (`serve.batcher.idle`, `.linger`, `.flush`): what the
instrumentation misses of the one thread that dispatches."""
from benchmark import program_spans

LAYER = "scheduler"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    covered = program_spans.window_share(
        obs, ("serve.batcher.idle_ms", "serve.batcher.linger_ms",
              "serve.batcher.flush_ms"))
    return None if covered is None else 100.0 - covered
