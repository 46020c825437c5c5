"""Program cost/memory model: compiled-executable FLOP/byte/HBM accounting.

Where analysis/flops.py counts dot_generals in the *jaxpr* (a structural
budget), this module prices the *compiled executable*: it AOT-compiles each
registry program via ``jit_fn.lower(*args).compile()`` and reads

  * ``cost_analysis()``   — flops and bytes-accessed of the optimized HLO
    (post-fusion, so bytes here are the real traffic estimate, unlike the
    unfused upper bound the old tools/flops_report.py printed);
  * ``memory_analysis()`` — argument / output / temp / alias buffer sizes,
    from which ``peak_hbm_bytes = argument + output + temp - alias`` (alias
    bytes are donated-input space the output reuses, counted once).

These numbers are deterministic per (program, jax version, platform), so
the ``cost_budget`` audit pass pins them exactly in the ``"cost"`` section
of tools/analysis_baseline.json with the same update discipline as the dot
budgets: a change in EITHER direction fails until `tools/audit.py
--update-baseline` re-records them in the same commit as the intentional
program change. This is the HBM-fit oracle the ROADMAP's MPMD-pipeline and
AOT-cold-start items need: "does this program's working set fit one chip"
becomes a table lookup instead of an OOM on silicon.

The roofline estimate prices a program against the published peaks of a
named chip (`CHIP_PEAKS`, keyed by jax's `device_kind`; a kind that is not
in the table is an error, never a default): expected step time is the max
of the compute and memory legs, and the binding leg names the bottleneck.
It is *reported* (pass details, flops_report) but never baseline-gated.

tools/flops_report.py is now a thin CLI shim over `attribution_report`
below (same precedent as tools/dtype_audit.py -> analysis/dtype.py).
"""

from __future__ import annotations

import sys
from typing import Dict, Optional

# keys pinned per program in analysis_baseline.json's "cost" section;
# append-only (removing or renaming one invalidates every checked-in entry)
COST_KEYS = ("flops", "bytes_accessed", "argument_bytes", "output_bytes",
             "temp_bytes", "alias_bytes", "peak_hbm_bytes")

# Published peaks of ONE chip, keyed by `jax.devices()[0].device_kind`,
# each with its source. The one table bench.py's physics audit and the
# roofline below both price against.
CHIP_PEAKS = {
    "TPU v5 lite": {
        "peak_tflops": 197.0,   # bf16
        "hbm_gbps": 819.0,
        "hbm_gb": 16.0,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  'bf16, 16 GB HBM2e at 819 GB/s per chip',
    },
}
# the chip the static cost model (cost_budget audit pass, pipeline planner)
# prices programs FOR: it runs on the CPU, where no device can be asked
MODEL_DEVICE_KIND = "TPU v5 lite"


def chip_model(device_kind: str) -> Dict[str, object]:
    """Published peaks of `device_kind`; an unknown kind is an error
    wherever a device number is priced, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it "
            f"to CHIP_PEAKS (analysis/costmodel.py) with its source; "
            f"known: {sorted(CHIP_PEAKS)}") from None


def compiled_cost(jit_fn, args) -> Dict[str, int]:
    """AOT-compile ``jit_fn(*args)`` and return the pinned cost dict
    (COST_KEYS). Works on CPU: XLA's cost and buffer-assignment analyses
    run on the optimized HLO regardless of backend."""
    compiled = jit_fn.lower(*args).compile()
    ca = dict(compiled.cost_analysis() or {})
    ma = compiled.memory_analysis()
    arg = int(getattr(ma, "argument_size_in_bytes", 0) or 0)
    out = int(getattr(ma, "output_size_in_bytes", 0) or 0)
    temp = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
    alias = int(getattr(ma, "alias_size_in_bytes", 0) or 0)
    return {
        "flops": int(ca.get("flops", 0) or 0),
        "bytes_accessed": int(ca.get("bytes accessed", 0) or 0),
        "argument_bytes": arg,
        "output_bytes": out,
        "temp_bytes": temp,
        "alias_bytes": alias,
        "peak_hbm_bytes": arg + out + temp - alias,
    }


def measure_program(program) -> Dict[str, int]:
    """`compiled_cost` over a registry Program's canonical arguments."""
    return compiled_cost(program.jit_fn, program.args_fn())


def roofline(cost: Dict[str, int],
             peak_tflops: Optional[float] = None,
             hbm_gbps: Optional[float] = None,
             device_kind: str = MODEL_DEVICE_KIND) -> Dict[str, object]:
    """Two-leg roofline: expected time is max(flops/peak, bytes/bandwidth),
    the binding leg is the bottleneck, and arithmetic intensity (flops per
    byte accessed) tells how far from the ridge the program sits."""
    chip = chip_model(device_kind)
    peak = peak_tflops if peak_tflops is not None else chip["peak_tflops"]
    bw = hbm_gbps if hbm_gbps is not None else chip["hbm_gbps"]
    compute_ms = cost["flops"] / (peak * 1e12) * 1e3
    memory_ms = cost["bytes_accessed"] / (bw * 1e9) * 1e3
    expected_ms = max(compute_ms, memory_ms)
    return {
        "compute_ms": compute_ms,
        "memory_ms": memory_ms,
        "expected_ms": expected_ms,
        "bound": "compute" if compute_ms >= memory_ms else "memory",
        "intensity_flops_per_byte": (
            cost["flops"] / cost["bytes_accessed"]
            if cost["bytes_accessed"] else float("inf")),
        "peak_tflops": peak,
        "hbm_gbps": bw,
    }


# ------------------------------------------------- flops_report attribution

V5E_BF16_PEAK_TFLOPS = CHIP_PEAKS[MODEL_DEVICE_KIND]["peak_tflops"]


def attribution_report(argv=None) -> None:
    """The original tools/flops_report.py body, relocated verbatim in
    behavior: static per-component cost attribution at the benchmark
    config, human table on stderr, JSON on stdout under --json. Uses the
    *lowered* (unfused) cost_analysis deliberately — its bytes column is
    the labeled upper bound the historical reports printed."""
    import json

    import jax
    jax.config.update("jax_platforms", "cpu")

    import bench
    from tools import microbench

    argv = sys.argv if argv is None else argv
    rows = {}

    def add(name, fn, *args):
        ca = jax.jit(fn).lower(*args).cost_analysis()
        rows[name] = {
            "tflops": round(ca.get("flops", float("nan")) / 1e12, 4),
            "gbytes_unfused_upper_bound": round(
                ca.get("bytes accessed", float("nan")) / 1e9, 2),
        }
        print("%-28s %8.4f TFLOP   %8.2f GB (unfused upper bound)"
              % (name, rows[name]["tflops"],
                 rows[name]["gbytes_unfused_upper_bound"]), file=sys.stderr)

    # full train step at the benchmark's headline variant (shared builder:
    # this attribution is of exactly the benchmarked program)
    trainer, state, batch = bench.build_variant_program("xla_b4")
    add("train_step_b4", trainer._train_step_impl, state, batch)

    # isolated components at the microbench shapes (B=2, S=32, 256x384)
    for case in ("encoder_fwd", "model_fwd", "warp_xla_fwd",
                 "warp_xla_fwdbwd", "comp_xla_fwd", "comp_xla_fwdbwd"):
        fn, args = microbench._case_fn(case)
        add(case + "_b2", fn, *args)

    step = rows["train_step_b4"]["tflops"]
    out = {
        "config": "LLFF 384x256 N=32 bf16 ResNet-50 (bench.py)",
        "components": rows,
        "peak_bound_images_per_sec": {
            "v5e_bf16_peak_tflops": V5E_BF16_PEAK_TFLOPS,
            "at_100pct_mxu": round(4 * V5E_BF16_PEAK_TFLOPS / step, 1),
            "at_40pct_mxu": round(0.4 * 4 * V5E_BF16_PEAK_TFLOPS / step, 1),
        },
    }
    # stdout JSON only under --json; the human-readable table already went
    # to stderr line by line via add()
    if "--json" in argv:
        print(json.dumps(out, indent=2))
    else:
        pb = out["peak_bound_images_per_sec"]
        print("peak-bound img/s: %.1f @100%% MXU, %.1f @40%% (v5e %.0f TFLOP/s)"
              % (pb["at_100pct_mxu"], pb["at_40pct_mxu"],
                 pb["v5e_bf16_peak_tflops"]), file=sys.stderr)
