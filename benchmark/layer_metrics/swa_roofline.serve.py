"""Kernels: the least time the chip could take for the traced steps'
sliding-window attention (benchmark/roofline_dots3.py: the pairs inside the
window at 2 * (256 + 128) a head, W_kvb over the chunk's own tokens, a
sequence's window rows read once) over the device time under
`lm_swa_prefill` + `lm_swa_decode` (the projections' scope, `lm_swa_proj`,
is weights against tokens and stands in `swa_ms.serve`)."""
from benchmark import roofline_dots3

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    return roofline_dots3.scope_share(obs, ("swa_prefill", "swa_decode"),
                                      roofline_dots3.swa_floor_s)
