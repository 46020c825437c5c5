"""Token server, attention over the selected rows on the device: self time
of the step programs' operations under `lm_dsa_prefill` (a chunk's queries)
and `lm_dsa_decode` (the decode rows): the absorbing products, the gather of
each query's selected latent rows, the softmax over them; ms a step over the
traced steps."""
from benchmark import lm_serve_spans

LAYER = "lm step"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    return lm_serve_spans.layer_ms_per_step(obs, ("dsa_prefill",
                                                  "dsa_decode"))
