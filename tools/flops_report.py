#!/usr/bin/env python
"""Static per-component cost attribution at the benchmark config (no TPU).

The measurement logic moved to mine_tpu/analysis/costmodel.py
(`attribution_report`), alongside the compiled-executable cost/memory
model behind the `cost_budget` audit pass — same retirement precedent as
tools/dtype_audit.py -> analysis/dtype.py. This shim keeps the CLI and its
output byte-compatible: the human-readable per-component table on stderr,
JSON on stdout under --json, and the peak-bound img/s line otherwise.

`jax.jit(fn).lower(args).cost_analysis()` on the HLO gives flops / bytes
for each component of the train step — the chip-free half of the time
attribution the round-1 verdict asked for (the on-chip halves are
tools/microbench.py and the bench profile). Flops are fusion-independent,
so these numbers hold for the TPU executable; 'bytes accessed' of the
UNFUSED lowering is only an upper bound and is labeled as such. (The
cost_budget pass pins the POST-fusion numbers per registry program in
tools/analysis_baseline.json.)

This is also the sanity denominator for throughput claims: images/sec
readings whose implied FLOP rate exceeds the chip's peak are measurement
artifacts (round-2 example, notes in git history: 226 img/s x 4.53
TFLOP/step = 256 TFLOP/s > the v5e's ~197 TFLOP/s bf16 peak => bogus).

Usage: python tools/flops_report.py [--json]
Runs on CPU (forced); ~10 min of tracing on a 1-core host.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mine_tpu.analysis.costmodel import (  # noqa: E402,F401 (compat re-export)
    V5E_BF16_PEAK_TFLOPS, attribution_report)


def main():
    attribution_report(sys.argv)


if __name__ == "__main__":
    main()
