"""Kernels: the least time the chip could take for the traced steps' decode
attention in latent space (benchmark/roofline_moe_mla.py: each sequence's
latent rows read ONCE for all 64 heads; the cache's bytes bind) over the
device time under `lm_mla_decode`."""
from benchmark import lm_serve_spans, roofline_moe_mla

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    return lm_serve_spans.roofline_share(
        obs, "mla_decode", roofline_moe_mla.mla_decode_floor_s)
