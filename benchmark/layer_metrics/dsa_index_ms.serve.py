"""Token server, the learned sparse selection on the device: self time of
the step programs' operations under `lm_dsa_index` (the indexer's
projections, LayerNorm, RoPE, the score kernel of a chunk's rows and the
decode rows' scores through their block tables) and `lm_dsa_select` (the
exact top-k: the bisection's passes over the scores, the compaction),
ms a step over the traced steps."""
from benchmark import lm_serve_spans

LAYER = "lm step"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    return lm_serve_spans.layer_ms_per_step(obs, ("dsa_index", "dsa_select"))
