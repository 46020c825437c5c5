"""Scheduler: the median per-view latency (scheduled arrival to result), over
all requests of the window. Near the knee it swings from run to run by more
than a bound of 10% could hold (PERF.md section 6), so it stands here without
one."""
LAYER = "scheduler"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "serve_views_per_s"


def read(obs):
    return obs["counters"].get("latency_p50_ms")
