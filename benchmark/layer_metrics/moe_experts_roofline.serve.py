"""Kernels: the least time the chip could take for the traced steps' held
experts (benchmark/roofline_moe_mla.py: 6 * h * I a held pair; each expert a
step touches has its weights read once; at ~43 rows an expert the weights'
bytes bind) over the device time under `lm_moe_experts` (sort, gather, the
three grouped products, the way back)."""
from benchmark import lm_serve_spans, roofline_moe_mla

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    return lm_serve_spans.roofline_share(
        obs, "moe_experts", roofline_moe_mla.moe_experts_floor_s)
