"""Token server, a step's latent attention on the device: self time of the
step programs' operations under `lm_mla_proj` (norms, projections, RoPE,
W_o), `lm_mla_prefill` (W_kvb over the cached prefix, the chunk's attention
kernel) and `lm_mla_decode` (the absorbing products, the paged kernel),
ms a step over the traced steps."""
from benchmark import lm_serve_spans

LAYER = "lm step"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    return lm_serve_spans.layer_ms_per_step(obs, lm_serve_spans.MLA)
