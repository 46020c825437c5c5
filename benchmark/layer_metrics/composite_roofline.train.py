"""Kernels: the least time the chip could take for one step's composite calls
(benchmark/roofline.py, from shapes: per call the larger of operations over
peak and bytes over bandwidth; forward and backward, every loss scale) over
the time those Pallas calls took in the trace. Memory binds every one of
them (PERF.md section 3)."""
from benchmark import roofline

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_images_per_s"


def read(obs):
    return roofline.train_share(obs, "composite")
