"""Token scheduler: tokens the window's steps held over their budget
(`serve.lm.step_tokens` / `serve.lm.step_budget`, 2,048 a step)."""
from benchmark import lm_serve_spans

LAYER = "lm scheduler"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "serve_views_per_s"


def read(obs):
    tokens = lm_serve_spans.counter_delta(obs, "serve.lm.step_tokens")
    budget = lm_serve_spans.counter_delta(obs, "serve.lm.step_budget")
    return None if not budget or tokens is None else 100.0 * tokens / budget
