"""Scheduler: the tail of the per-view latency (scheduled arrival to result,
a failed request infinitely late), over all requests of the window. At a
rate near the knee one burst of arrivals decides it, so it swings from run
to run by more than any bound could hold (PERF.md section 2) and stands here
without one."""
LAYER = "scheduler"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "serve_views_per_s"


def read(obs):
    return obs["counters"].get("latency_p95_ms")
