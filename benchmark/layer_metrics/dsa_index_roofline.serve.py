"""Kernels: the least time the chip could take for the traced steps'
indexer (benchmark/roofline_dots3.py: 2 * 128 * 64 a scored (query, key)
pair, every sequence's index keys read once; compute binds) over the device
time under `lm_dsa_index` + `lm_dsa_select` (the XLA operations of the two
scopes included: the selection's passes over the score matrix are time, and
no part of the floor)."""
from benchmark import roofline_dots3

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    return roofline_dots3.scope_share(obs, ("dsa_index", "dsa_select"),
                                      roofline_dots3.dsa_index_floor_s)
