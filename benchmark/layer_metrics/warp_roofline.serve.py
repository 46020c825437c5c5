"""Kernels: the least time the chip could take for the warp calls of the
views completed in the traced window (benchmark/roofline.py: one forward
call per view, from shapes) over the time those Pallas calls took there.
Padded pose slots are device work no view needs, so they lower the share."""
from benchmark import roofline

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    return roofline.serve_share(obs, "warp")
