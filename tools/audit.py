#!/usr/bin/env python
"""Program auditor: run the static-analysis pass suite over every core
jitted program (train step, fused loss fwd/bwd, the two warp backends,
the serve render engine single-device and mesh, eval encode).

Passes (mine_tpu/analysis/passes.py):
  dtype_upcast     bf16->f32 converts inside conv-stack scopes
  dot_budget       dot_general count / FLOPs vs tools/analysis_baseline.json
  cost_budget      compiled flops/bytes/peak-HBM vs the baseline "cost"
                   section (AOT compile + cost/memory_analysis + roofline)
  recompile_churn  identically-shaped re-dispatch must hit the jit cache
  transfer_guard   hot paths clean under jax.transfer_guard("disallow")
  donation         donated buffers actually consumed (deleted, no warning)
  concurrency      lock order + thread leaks over a live threaded workload
  aot_staleness    serving AOT executable store current for this jax
                   version / backend / topology (MINE_TPU_AOT_STORE;
                   skips when no store is configured)

Usage:
  python tools/audit.py --gate                # CI gate: everything, exit 1 on any FAIL
  python tools/audit.py --list                # registered programs and passes
  python tools/audit.py --selftest            # prove each pass detects its seeded violation
  python tools/audit.py --programs warp_xla,serve_render
  python tools/audit.py --passes dot_budget,donation
  python tools/audit.py --update-baseline     # rewrite analysis_baseline.json
                                              # (green runs only, commit with the change)

Runs entirely on the CPU container (tiny canonical shapes, fake 8-device
mesh) in a few minutes; wired into tools/verify_tier1.sh as a loud gate.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# same CPU setup as tests/conftest.py: a fake 8-device mesh for the
# mesh-serve program, on the CPU unless JAX_PLATFORMS says otherwise
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

from mine_tpu.analysis import framework, passes as passes_mod  # noqa: E402
from mine_tpu.analysis import programs as programs_mod  # noqa: E402


def _select_passes(names, baseline):
    suite = passes_mod.default_passes(baseline)
    if not names:
        return suite
    by_name = {p.name: p for p in suite}
    missing = [n for n in names if n not in by_name]
    if missing:
        raise SystemExit(f"unknown pass(es): {', '.join(missing)} "
                         f"(have: {', '.join(by_name)})")
    return [by_name[n] for n in names]


def _select_programs(names):
    all_names = programs_mod.program_names()
    if not names:
        return programs_mod.get_programs()
    missing = [n for n in names if n not in all_names]
    if missing:
        raise SystemExit(f"unknown program(s): {', '.join(missing)} "
                         f"(have: {', '.join(all_names)})")
    return programs_mod.get_programs(names)


def _cmd_list():
    baseline = framework.load_baseline()
    print("programs:")
    for n in programs_mod.program_names():
        mark = " " if (n in baseline.get("programs", {})
                       and n in baseline.get("cost", {})) else "*"
        print(f"  {mark} {n}")
    print("  (* = no baseline entry yet; run --update-baseline)")
    print("passes:")
    for p in passes_mod.default_passes(baseline):
        print(f"    {p.name} ({p.scope})")
    return 0


def _cmd_selftest():
    """Each pass runs against its own seeded violation fixture and MUST
    fail on it — proving the lint detects what it claims to. A selftest
    that comes back ok means the detector is blind: exit 1."""
    blind = 0
    for p in passes_mod.default_passes({"programs": {}, "budgets": {},
                                        "cost": {}}):
        r = p.selftest()
        detected = not r.ok
        status = "detected" if detected else "MISSED"
        print(f"[{status:>8}] {p.name:<16} {r.details}")
        if not detected:
            blind += 1
    if blind:
        print(f"selftest: {blind} pass(es) failed to detect their seeded "
              f"violation — the lint is blind, fix before trusting --gate")
        return 1
    print("selftest: every pass detected its seeded violation")
    return 0


def _cmd_update_baseline(path, program_names):
    baseline = framework.load_baseline(path)
    budget_pass = passes_mod.DotBudgetPass(baseline)
    cost_pass = passes_mod.CostBudgetPass(baseline)
    progs = _select_programs(program_names)
    for prog in progs:
        measured = budget_pass.measure(prog)
        baseline["programs"][prog.name] = measured
        cost = cost_pass.measure(prog)
        baseline["cost"][prog.name] = cost
        det = ", ".join(f"{k}={v}" for k, v in sorted(measured.items()))
        print(f"  {prog.name:<20} {det}")
        print(f"  {'':<20} cost: flops={cost['flops']} "
              f"bytes={cost['bytes_accessed']} "
              f"peak_hbm={cost['peak_hbm_bytes']}")
    # seed the cross-cutting budgets the tests consume on first write;
    # existing values are preserved (edit them deliberately, with a
    # CHANGES.md line saying why)
    defaults = {
        # PR-2 fused-loss acceptance gate: 8 Toeplitz blur einsums fused
        # vs 80 in the per-scale reference pyramid (>=4x reduction)
        "fused_loss.blur_dots": 8,
        "fused_loss.blur_dots_reference": 80,
    }
    for k, v in defaults.items():
        baseline["budgets"].setdefault(k, v)
    framework.save_baseline(baseline, path)
    print(f"wrote {path} ({len(baseline['programs'])} programs)")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--gate", action="store_true",
                    help="run everything; exit 1 on any failure (CI mode)")
    ap.add_argument("--list", action="store_true",
                    help="list registered programs and passes")
    ap.add_argument("--selftest", action="store_true",
                    help="run each pass's seeded-violation fixture; every "
                         "pass must DETECT its violation")
    ap.add_argument("--programs", default="",
                    help="comma-separated program subset (default: all)")
    ap.add_argument("--passes", default="",
                    help="comma-separated pass subset (default: all)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="re-measure dot/FLOP budgets and rewrite the "
                         "baseline file (green runs only)")
    ap.add_argument("--baseline", default=framework.DEFAULT_BASELINE_PATH,
                    help="baseline JSON path (default: "
                         "tools/analysis_baseline.json)")
    args = ap.parse_args(argv)

    prog_names = [n for n in args.programs.split(",") if n]
    pass_names = [n for n in args.passes.split(",") if n]

    if args.list:
        return _cmd_list()
    if args.selftest:
        return _cmd_selftest()
    if args.update_baseline:
        return _cmd_update_baseline(args.baseline, prog_names)

    baseline = framework.load_baseline(args.baseline)
    suite = _select_passes(pass_names, baseline)
    progs = _select_programs(prog_names)
    results = framework.run_audit(progs, suite)
    print(framework.format_report(results))
    failed = [r for r in results if not r.ok]
    if failed and args.gate:
        print("AUDIT GATE: FAILED — fix the program or, for an intentional "
              "budget change, rerun tools/audit.py --update-baseline and "
              "commit the new baseline with a CHANGES.md line.")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
