"""Token scheduler: mean host time to compose a step (admission, pages,
eviction, the plan), from the program's `serve.lm.schedule` span."""
from benchmark import harness

LAYER = "lm scheduler"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    return harness.registry_window_mean(obs["registry"],
                                        "serve.lm.schedule_ms")
