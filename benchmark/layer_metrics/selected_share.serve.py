"""Learned sparse attention: the (query, key) pairs the full layers attended
over the causal pairs they scored, over the window's steps (`serve.lm.step`
spans: `selected_pairs` / `index_pairs`): what the selection leaves of dense
attention's reading. 100 where every context is within `index_topk`."""
from benchmark import lm_serve_spans

LAYER = "lm step"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_views_per_s"


def read(obs):
    steps = lm_serve_spans.window_steps(obs)
    if not steps or "selected_pairs" not in steps[0]:
        return None
    scored = sum(s["index_pairs"] for s in steps)
    return (100.0 * sum(s["selected_pairs"] for s in steps) / scored
            if scored else None)
