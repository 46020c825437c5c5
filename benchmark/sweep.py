#!/usr/bin/env python
"""Find the knee of a serving cell, once, on the chip.

  python benchmark/sweep.py --workload llff_serve_steady --seed 1 \
      [--seconds 12] [--fractions 0.6,0.8,0.9,1.0,1.1]

Not part of a check: a `benchmark` PR runs it when it defines a serving cell
(or when the program's capacity has moved so far that the frozen rate no
longer sits below the knee), reads the table, and writes 0.8 of the knee
into the cell's traffic file as a number. One process, one set-up: first the
closed-loop capacity (two full batches kept outstanding), then the cell's
own open loop at fixed fractions of it.

A rate is SUSTAINED when the backlog does not grow through the window: the
median latency of the last third of the requests is at most 1.5 times that
of the first third plus 5 ms, and what is still queued or in flight when the
window closes is at most twice what Little's law gives a steady queue (rate
x median latency). The knee is the highest sustained rate.
"""

import argparse
import concurrent.futures
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def closed_loop(ctx, seconds, outstanding):
    """Views per second with `outstanding` requests always in flight."""
    batcher, ids, poses = ctx["batcher"], ctx["ids"], ctx["poses"]
    pending, done, k = set(), 0, 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        while len(pending) < outstanding:
            pending.add(batcher.submit(ids[(k * 37) % len(ids)],
                                       poses[(k * 7) % len(poses)]))
            k += 1
        finished, pending = concurrent.futures.wait(
            pending, return_when=concurrent.futures.FIRST_COMPLETED)
        for fut in finished:
            fut.result()
        done += len(finished)
    window = time.perf_counter() - t0
    for fut in pending:
        fut.result()
    return done / window


def sustained(result, offered):
    lat = [x for x in result["lat_ms"]]
    third = max(1, len(lat) // 3)
    first = statistics.median(lat[:third])
    last = statistics.median(lat[-third:])
    steady_backlog = (offered / result["window_s"]
                      * statistics.median(lat) / 1e3)
    return (result["counters"]["backlog_at_end"] <= 2.0 * steady_backlog + 1
            and last <= 1.5 * first + 5.0, first, last)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--fractions", default="0.6,0.8,0.9,1.0,1.1")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell, watch, devices, _ = harness.open_cell(args.workload)
    spans = harness.Spans()
    driver = cell.driver()
    ctx = driver.setup(cell, args.seed, devices, spans)
    outstanding = 2 * ctx["serve_cfg"].max_requests
    capacity = closed_loop(ctx, args.seconds, outstanding)
    harness.say("closed loop, %d outstanding: %.2f views/s"
                % (outstanding, capacity))
    rows = []
    for frac in [float(x) for x in args.fractions.split(",")]:
        rate = frac * capacity
        cell.workload = dict(cell.workload, rate_views_per_s=rate,
                             reference_views=0 if rows else 2)
        result = driver.measure(ctx, args.seconds, None, watch)
        ok, first, last = sustained(result, result["attempted"])
        rows.append({
            "fraction_of_closed_loop": frac, "rate_views_per_s": rate,
            "offered": result["attempted"],
            "completed_in_window": result["counters"]["views_in_window"],
            "backlog_at_end": result["counters"]["backlog_at_end"],
            "p50_ms": result["end_to_end"]["serve_latency_p50_ms"],
            "p95_ms": result["end_to_end"]["serve_latency_p95_ms"],
            "first_third_median_ms": first, "last_third_median_ms": last,
            "gen_late_p95_ms": result["counters"]["gen_late_p95_ms"],
            "sustained": ok})
        harness.say("rate %.2f (%.2f of closed loop): %s" % (
            rate, frac, rows[-1]))
    driver.teardown(ctx)
    knee = max([r["rate_views_per_s"] for r in rows if r["sustained"]],
               default=None)
    out = {"cell": cell.name, "closed_loop_views_per_s": capacity,
           "seconds": args.seconds, "rows": rows, "knee_views_per_s": knee,
           "rate_at_0.8_of_knee": None if knee is None else 0.8 * knee,
           "device": harness.device_block(devices)}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
