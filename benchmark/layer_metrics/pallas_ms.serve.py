"""Kernels: device time of the Pallas custom calls (warp and composite) in
the traced window, per view completed in it."""
from benchmark import trace_reduce

LAYER = "kernels"
UNIT = "ms/view"
SOURCE = "device_trace"
MOVES = "serve_views_per_s"


def read(obs):
    views = obs["counters"].get("views_in_trace_window")
    if obs["trace"] is None or not views:
        return None
    secs = trace_reduce.op_seconds(obs["trace"], trace_reduce.is_pallas_call)
    return secs / views * 1e3 if secs else None
