"""Scheduler: views rendered over the padded pose slots dispatched, from the
program's `serve.batcher.coalesce_size` histogram (its edges are the
engine's power-of-two pose buckets, so a batch of n fills n of the slots of
the bucket it landed in)."""
LAYER = "scheduler"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "serve_views_per_s"
HISTOGRAM = "serve.batcher.coalesce_size"


def read(obs):
    reg = obs["registry"]
    a = reg.get("start", {}).get(HISTOGRAM + "#buckets")
    b = reg.get("end", {}).get(HISTOGRAM + "#buckets")
    views_a = (reg.get("start", {}).get(HISTOGRAM) or {}).get("sum", 0.0)
    views_b = (reg.get("end", {}).get(HISTOGRAM) or {}).get("sum", 0.0)
    if not b:
        return None
    edges, counts_b = b
    counts_a = a[1] if a else [0] * len(counts_b)
    slots = sum(edge * (cb - ca)
                for edge, cb, ca in zip(edges, counts_b, counts_a))
    return 100.0 * (views_b - views_a) / slots if slots > 0 else None
