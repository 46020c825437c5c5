"""Kernels: the least time the chip could take for the traced steps' prompt
chunks' attention (benchmark/roofline_moe_mla.py: the chunk's query-key
pairs at 2 * (192 + 128) a head, W_kvb over the chunk's own tokens, the
sequence's latent rows read once; compute binds) over the device time under
`lm_mla_prefill`. The scope up-projects the whole cached prefix again for
every chunk: that is recomputation, its time counts, the floor does not
hold it."""
from benchmark import lm_serve_spans, roofline_moe_mla

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    return lm_serve_spans.roofline_share(
        obs, "mla_prefill", roofline_moe_mla.mla_prefill_floor_s)
