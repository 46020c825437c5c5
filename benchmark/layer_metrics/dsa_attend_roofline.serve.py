"""Kernels: the least time the chip could take for the traced steps'
attention over the selected rows (benchmark/roofline_dots3.py: a selected
pair in latent space at 2 * (576 + 512) a head, a selected row of 1,152
bytes read once for all heads, the absorbing products a token) over the
device time under `lm_dsa_prefill` + `lm_dsa_decode` (the gather
included)."""
from benchmark import roofline_dots3

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    return roofline_dots3.scope_share(obs, ("dsa_prefill", "dsa_decode"),
                                      roofline_dots3.dsa_attend_floor_s)
