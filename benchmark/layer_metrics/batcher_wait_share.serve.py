"""Scheduler: share of the window the batcher's thread (the one thread that
dispatches) spent with nothing to dispatch: `serve.batcher.idle` (empty
queue) plus `serve.batcher.linger` (waiting for co-riders or the oldest
deadline). No request was due: the device idles for want of work."""
from benchmark import program_spans

LAYER = "scheduler"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    return program_spans.window_share(
        obs, ("serve.batcher.idle_ms", "serve.batcher.linger_ms"))
