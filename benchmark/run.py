#!/usr/bin/env python
"""One cell of the benchmark, on the machine this is started on.

  python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. It holds the cell's chips, builds the cell from its files
(BENCHMARK.json -> benchmark/traffic/<traffic>.json, the configuration's file ->
benchmark/drivers/<driver>.py), warms the shapes the window will use,
measures for --seconds, and prints as its last line one JSON object:
`correct`, `attempted`, `failed`, `metrics`, `device`, and with --trace 1
`breakdown`. With --trace 0 the metrics are the cell's end-to-end metrics;
--trace 1 is a run of its own, with a profiler window inside the measured
time, and gives the cell's per-layer metrics.

No TPU, fewer chips than the cell asks for, or a device kind that
benchmark/peaks.json does not hold: exit code 1 and no result line.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402  (no JAX yet)


def run(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default="",
                    help="keep the raw profiler trace here (for reading one "
                         "by hand); default: a temporary directory, removed")
    args = ap.parse_args(argv)
    harness.process_start_time()

    try:
        if importlib.util.find_spec("mine_tpu") is None:
            raise harness.BenchError(
                "this checkout holds the benchmark and no program "
                "(no mine_tpu package beside benchmark/)")
        cell, watch, devices, peaks = harness.open_cell(args.workload)
        harness.say("cell %s on %d x %s (%s); seed %d; compile cache %s"
                    % (cell.name, len(devices), peaks["kind"],
                       devices[0].platform, args.seed,
                       harness.COMPILE_CACHE_DIR))
        spans = harness.Spans()
        driver = cell.driver()
        ctx = driver.setup(cell, args.seed, devices, spans)
        tracer = None
        if args.trace:
            tracer = harness.TraceWindow(
                float(cell.workload.get("trace_seconds", 3.0)),
                keep_dir=args.trace_dir)
        result = driver.measure(ctx, args.seconds, tracer, watch)
        driver.teardown(ctx)
    except harness.BenchError as e:
        sys.stderr.write("benchmark: %s\n" % e)
        return 1

    setup_s = result["window_start"] - harness.process_start_time()
    cache = watch.summary()
    harness.say("set-up %.2fs; window %.2fs; compile requests in all: %d "
                "from the cache, %d compiled %s"
                % (setup_s, result["window_s"], cache["hits"],
                   cache["misses"], cache["compiled"]))
    device = harness.device_block(devices, result.get("temp_bytes", 0))
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": {}, "device": device}
    if not args.trace:
        values = dict(result["end_to_end"], setup_s=setup_s)
        for m in cell.metric_entries("end_to_end"):
            if values.get(m["name"]) is not None:
                line["metrics"][m["name"]] = {
                    "value": float(values[m["name"]]), "unit": m["unit"]}
    else:
        t0 = time.perf_counter()
        reduced = tracer.reduced()
        harness.say("trace reduced in %.1fs: %s" % (
            time.perf_counter() - t0,
            "no device plane" if reduced is None else
            "window %.3fs, busy %.3fs" % (reduced["window_s"],
                                          reduced["busy_s"])))
        obs = {"trace": reduced, "spans": spans.snapshot(),
               "counters": result.get("counters", {}),
               "registry": result.get("registry", {}),
               "shapes": result.get("shapes", {}), "peaks": peaks,
               "window_s": result["window_s"], "cell": cell.name}
        for m in cell.metric_entries("per_layer"):
            value = cell.layer_reader(m["name"]).read(obs)
            if value is not None:
                line["metrics"][m["name"]] = {"value": float(value),
                                              "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
    for name, detail in sorted(result.get("details", {}).items()):
        harness.say("%s: %s" % (name, detail))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
