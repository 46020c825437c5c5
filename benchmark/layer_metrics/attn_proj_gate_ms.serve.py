"""Token server, what the attention sub-layers run outside their kinds' own
metrics where outputs are gated: self time of the step programs' operations
under `lm_mla_proj` (a full layer's norms, projections, RoPE and W_o; a
sliding layer's are `lm_swa_proj`, in `swa_ms.serve`) and `lm_attn_gate`
(the headwise sigmoid gate of both kinds), ms a step over the traced steps.
Nothing where the program has no gate's scope (a model without one reads
its projections in `mla_ms.serve`)."""
from benchmark import lm_serve_spans

LAYER = "lm step"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    found = lm_serve_spans.device_by_layer(obs)
    if not found or not found["layers"].get("attn_gate"):
        return None
    return lm_serve_spans.layer_ms_per_step(obs, ("mla_proj", "attn_gate"))
