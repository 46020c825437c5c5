"""Latent cache: share of the admitted requests' prompt tokens that were
read from a resident document's pages and not prefilled
(`serve.lm.prompt_tokens_cached` / `serve.lm.prompt_tokens`)."""
from benchmark import lm_serve_spans

LAYER = "latent cache"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "serve_views_per_s"


def read(obs):
    cached = lm_serve_spans.counter_delta(obs, "serve.lm.prompt_tokens_cached")
    prompt = lm_serve_spans.counter_delta(obs, "serve.lm.prompt_tokens")
    return None if not prompt or cached is None else 100.0 * cached / prompt
