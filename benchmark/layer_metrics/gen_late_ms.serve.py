"""Load generator: how late the benchmark's own thread submitted, p95 of
(actual - scheduled) submit time. A starved generator must not be read as a
fast server."""
LAYER = "load generator"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "serve_views_per_s"


def read(obs):
    return obs["counters"].get("gen_late_p95_ms")
