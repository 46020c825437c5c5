"""Collectives: time per step in which a collective operation held a device
and no compute ran on it (only cells across chips report it)."""
from benchmark import trace_reduce

LAYER = "collectives"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_images_per_s"


def read(obs):
    trace = obs["trace"]
    if trace is None or len(trace["devices"]) < 2:
        return None
    runs = trace_reduce.module_runs(
        trace, obs["counters"].get("step_program", "train_step"))
    if not runs:
        return None
    lo = min(s for d in trace["devices"] for s, _, n in d["modules"])
    hi = max(e for d in trace["devices"] for _, e, n in d["modules"])
    exposed = trace_reduce.exposed_seconds(
        trace, trace_reduce.is_collective, lo, hi)
    return exposed / runs * 1e3
