"""Token server, the sliding-window layers' attention on the device: self
time of the step programs' operations under `lm_swa_proj` (norms,
projections, RoPE, W_o), `lm_swa_prefill` (W_kvb over the chunk and its
window, the window kernel) and `lm_swa_decode` (the gathered window in the
absorbed form), ms a step over the traced steps."""
from benchmark import lm_serve_spans

LAYER = "lm step"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    return lm_serve_spans.layer_ms_per_step(
        obs, ("swa_proj", "swa_prefill", "swa_decode"))
