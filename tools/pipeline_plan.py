#!/usr/bin/env python
"""Pipeline stage planner CLI: propose `training.pipeline.stages` /
`training.pipeline.microbatches` for the staged train step
(mine_tpu/parallel/pipeline.py) under a declared per-chip HBM budget.

The plan consumes the cost model's rows for the four stage sub-programs
(pipe_encode / pipe_decode / pipe_render / pipe_loss — XLA's own
post-fusion flops/bytes/peak-HBM from analysis/costmodel.py). By default
the rows come from the pinned audit baseline (tools/analysis_baseline.json,
maintained by tools/audit.py --update-baseline), so planning is instant
and reproducible; --measure AOT-compiles the stage programs live instead
(canonical tiny shapes on CPU, the flagship shape on a real chip).

Per-stage peak-HBM is the EXACT integer sum of the member programs' cost
rows (mine_tpu/analysis/planner.py documents the bound); step-time
estimates are the costmodel roofline at the published peaks of the chip
the model prices for (analysis/costmodel.py CHIP_PEAKS).

Usage:
  python tools/pipeline_plan.py --budget-gb 16
  python tools/pipeline_plan.py --budget-gb 16 --max-stages 2 --json
  python tools/pipeline_plan.py --budget-gb 16 --measure
  MINE_TPU_PIPELINE_HBM_BUDGET_GB=16 python tools/pipeline_plan.py

Exit status: 0 with a plan, 2 when the budget is infeasible (the same
condition the `pipeline_plan` audit pass gates on), 1 on missing rows.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "analysis_baseline.json")


def _measured_table():
    """AOT-compile the four stage programs and measure them live."""
    from mine_tpu.analysis import costmodel
    from mine_tpu.analysis import planner
    from mine_tpu.analysis.programs import get_program
    return {name: costmodel.measure_program(get_program(name))
            for name in planner.PIPE_PROGRAMS}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="plan pipeline stage cuts under an HBM budget")
    ap.add_argument("--budget-gb", type=float,
                    default=float(os.environ.get(
                        "MINE_TPU_PIPELINE_HBM_BUDGET_GB", 16.0)),
                    help="per-chip HBM budget in GiB (default: "
                         "$MINE_TPU_PIPELINE_HBM_BUDGET_GB or 16)")
    ap.add_argument("--max-stages", type=int, default=4,
                    help="largest stage count to consider (<= 4)")
    ap.add_argument("--baseline", default=BASELINE,
                    help="audit baseline JSON with the pipe_* cost rows")
    ap.add_argument("--measure", action="store_true",
                    help="AOT-compile the stage programs and measure live "
                         "instead of reading the baseline")
    ap.add_argument("--json", action="store_true",
                    help="emit the plan as JSON on stdout")
    args = ap.parse_args(argv)

    from mine_tpu.analysis import planner

    if args.measure:
        table = _measured_table()
    else:
        try:
            with open(args.baseline, encoding="utf-8") as f:
                table = json.load(f).get("cost", {})
        except FileNotFoundError:
            print(f"baseline not found: {args.baseline} (run tools/audit.py "
                  f"--update-baseline, or pass --measure)", file=sys.stderr)
            return 1

    budget = int(args.budget_gb * 2 ** 30)
    try:
        plan = planner.plan_stages(table, budget,
                                   max_stages=args.max_stages)
    except KeyError as e:
        print(f"pipeline_plan: {e}", file=sys.stderr)
        return 1
    except planner.PlanInfeasibleError as e:
        print(f"pipeline_plan: INFEASIBLE: {e}", file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps(plan, indent=2, sort_keys=True))
        return 0

    print(f"pipeline plan @ budget {args.budget_gb:.1f} GiB/chip "
          f"({'measured live' if args.measure else 'baseline rows'}):")
    for i, st in enumerate(plan["per_stage"]):
        names = " + ".join(n.removeprefix("pipe_") for n in st["programs"])
        print(f"  stage {i}: {names:24s} peak_hbm="
              f"{st['peak_hbm_bytes']:>12d} B "
              f"({st['peak_hbm_bytes'] / 2 ** 20:8.1f} MiB)  "
              f"expected {st['expected_ms']:.3f} ms")
    print(f"  -> training.pipeline.stages={plan['stages']} "
          f"training.pipeline.microbatches={plan['microbatches']} "
          f"(bottleneck {plan['bottleneck_ms']:.3f} ms, fill "
          f"{plan['total_ms']:.3f} ms)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
