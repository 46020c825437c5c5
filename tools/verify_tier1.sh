#!/usr/bin/env bash
# Canonical tier-1 verification — the pytest line the driver runs
# (/root/TESTS_LAST_RUN.json: 6 xdist workers, --dist loadfile, 1470 s;
# the serial 870 s line in ROADMAP.md no longer fits the suite) plus -rX,
# wrapped so builders and CI run one command and get a pass-count delta
# against the checked-in baseline instead of eyeballing dots. Exit code is
# the pytest exit code; the DOTS_PASSED line at the end is the count.
#
# Usage: tools/verify_tier1.sh [--update-baseline]
#   --update-baseline  on a GREEN run (pytest rc=0, no regression, no
#                      XPASS) write the measured pass count to
#                      tools/tier1_baseline.txt — the sanctioned way to
#                      bump the baseline in the same commit as an
#                      intentional test-count change (with a CHANGES.md
#                      line saying why). Never writes on a red run.
# Baseline: tools/tier1_baseline.txt.
#
# XPASS policy: the suite carries strict=False xfails documenting a real
# environment bug — the 8-device GSPMD CPU-mesh numeric divergence. Two of
# them (test_plane_scan.py::test_train_step_plane_scan_matches_xla and
# test_train.py::test_train_step_pallas_backends_on_mesh) NEVER pass on
# the broken partitioner, so their XPASS means the environment changed
# under us (e.g. a jax upgrade fixed the divergence) and all four 8-device
# xfails must be retired — that XPASS fails THIS wrapper loudly instead of
# vanishing into the dot stream. The other two (the sharded train/eval
# parity tests in test_train.py) xpass nondeterministically — the drift
# ranges 0.4%-4x across processes on the SAME build — so their XPASS is
# reported but does not redden the run.
set -o pipefail
cd "$(dirname "$0")/.."

UPDATE_BASELINE=0
[ "${1:-}" = "--update-baseline" ] && UPDATE_BASELINE=1

LOG=/tmp/_t1.log
EVENTS=/tmp/_t1_events.jsonl
rm -f "$LOG" "$EVENTS"
# funnel every telemetry event the suite emits into one stream so the
# schema-validation pass below can gate on it (events are additive — the
# suite behaves identically with or without the sink)
timeout -k 10 1470 env JAX_PLATFORMS=cpu \
    MINE_TPU_TELEMETRY_EVENTS="$EVENTS" python -m pytest tests/ -q -rX \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p xdist -n 6 --dist loadfile -p no:randomly --durations=15 2>&1 \
    | tee "$LOG"
rc=${PIPESTATUS[0]}

# every line of the event stream must satisfy the mtpu-ev1 schema — a
# subsystem that emits malformed events fails tier-1 loudly here. --strict
# additionally pins every documented kind's payload (events.KIND_FIELDS):
# the schema-drift tripwire for the append-only mtpu-ev1 contract.
if ! python tools/validate_events.py --allow-missing --strict "$EVENTS"; then
    echo "EVENT_SCHEMA: telemetry event stream failed validation ($EVENTS)"
    [ "$rc" -eq 0 ] && rc=1
fi

# the reporting path itself is CI smoke: obs_report must render the
# suite's funneled stream without crashing (mirrors the validate gate —
# a report bug would otherwise only surface when a human needs the report)
if [ -f "$EVENTS" ]; then
    if ! python tools/obs_report.py "$EVENTS" > /tmp/_t1_obs_report.txt; then
        echo "OBS_REPORT: tools/obs_report.py failed on the suite's event" \
             "stream ($EVENTS — report attempt in /tmp/_t1_obs_report.txt)"
        [ "$rc" -eq 0 ] && rc=1
    fi
fi

# the program auditor is part of tier-1: every registered jitted program
# must hold its dtype/budget/churn/transfer/donation/concurrency contracts
# (tools/analysis_baseline.json is the budget source of truth; bump it via
# `tools/audit.py --update-baseline` in the same commit as the intentional
# program change, with a CHANGES.md line saying why)
if ! timeout -k 10 600 python tools/audit.py --gate \
        > /tmp/_t1_audit.txt 2>&1; then
    tail -20 /tmp/_t1_audit.txt
    echo "AUDIT: tools/audit.py --gate failed (full report in" \
         "/tmp/_t1_audit.txt)"
    [ "$rc" -eq 0 ] && rc=1
fi

# the staged-pipeline numerics contract is tier-1 in its own right: the
# wall-capped pytest window above truncates into the heavy train suites on
# a slow box (ROADMAP "dots window vs box speed"), so the pipeline-off
# bitwise bar and the staged-1x1-vs-fused parity bar are re-gated
# explicitly here — a train-step or loss-split change that breaks the
# staged decomposition fails tier-1 even when the window axed the suite
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
        "tests/test_train_pipeline.py::test_pipeline_off_default_routes_fused_bitwise" \
        "tests/test_train_pipeline.py::test_staged_1x1_matches_fused" \
        -q -p no:cacheprovider -p no:randomly \
        > /tmp/_t1_pipeline.txt 2>&1; then
    tail -20 /tmp/_t1_pipeline.txt
    echo "PIPELINE: staged-vs-fused parity gate failed (output in" \
         "/tmp/_t1_pipeline.txt)"
    [ "$rc" -eq 0 ] && rc=1
fi

# the partition-safety property is tier-1 in its own right (same
# rationale as the pipeline gate above: the wall-capped window can
# truncate before test_serve_net.py on a slow box): under an asymmetric
# partition every front must resolve exactly ONE alive owner per key
# with membership single-writer (no split-brain), and the heal must
# re-converge every owner map — a ring/hostnet change that breaks
# either fails tier-1 even when the window axed the suite
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
        "tests/test_serve_net.py::test_partition_one_alive_owner_per_key" \
        "tests/test_serve_net.py::test_partition_heal_reconverges" \
        -q -p no:cacheprovider -p no:randomly \
        > /tmp/_t1_partition.txt 2>&1; then
    tail -20 /tmp/_t1_partition.txt
    echo "PARTITION: split-brain/heal property gate failed (output in" \
         "/tmp/_t1_partition.txt)"
    [ "$rc" -eq 0 ] && rc=1
fi

# the binary wire fabric's safety core is tier-1 (same wall-cap
# rationale): wire OFF must stay byte-identical to the PR-19 JSON wire,
# bin_f32 must be end-to-end bitwise vs JSON, hostile/truncated frames
# must be rejected-and-retried (never crashed on), and the coalescer
# must return every envelope to its own caller in order — a wire.py or
# hostnet/ring regression on any of these fails tier-1 even when the
# window axed tests/test_serve_wire.py
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
        "tests/test_serve_wire.py::test_wire_off_payload_byte_identical_to_pr19" \
        "tests/test_serve_wire.py::test_bin_f32_end_to_end_bitwise_vs_json" \
        "tests/test_serve_wire.py::test_truncated_binary_frame_retried_not_crashed" \
        "tests/test_serve_wire.py::test_hostile_binary_frame_rejected_with_400" \
        "tests/test_serve_wire.py::test_coalesced_batch_ordering_under_mixed_tiers" \
        -q -p no:cacheprovider -p no:randomly \
        > /tmp/_t1_wire.txt 2>&1; then
    tail -20 /tmp/_t1_wire.txt
    echo "WIRE: binary wire-fabric safety gate failed (output in" \
         "/tmp/_t1_wire.txt)"
    [ "$rc" -eq 0 ] && rc=1
fi

# the incident-bundle capture/read contract is tier-1: postmortem's
# selftest pushes a synthetic incident through the REAL FlightRecorder
# dump path, renders it, and asserts a corrupted copy is rejected — so a
# bundle-format drift between recorder.py and tools/postmortem.py fails
# here, not during an actual incident
if ! timeout -k 10 120 python tools/postmortem.py --selftest \
        > /tmp/_t1_postmortem.txt 2>&1; then
    tail -20 /tmp/_t1_postmortem.txt
    echo "POSTMORTEM: tools/postmortem.py --selftest failed (output in" \
         "/tmp/_t1_postmortem.txt)"
    [ "$rc" -eq 0 ] && rc=1
fi

# any checked-in bench JSON (a conductor-written mtpu-bench1 round; the
# round 1-5 driver wrappers left the tree in PR 24) must stay parseable by
# tools/bench_conductor.py, which diffs future sweeps against them
if ! python tools/bench_conductor.py --check-schema; then
    echo "BENCH_SCHEMA: a checked-in BENCH_r*.json fails" \
         "tools/bench_conductor.py --check-schema"
    [ "$rc" -eq 0 ] && rc=1
fi

# 'X' (xpass) joins the dot classes so an xpassing line can't silently
# swallow its neighbors' dots from the count
passed=$(grep -aE '^[.FEsxX]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)
xpassed=$(grep -aoE '[0-9]+ xpassed' "$LOG" | tail -1 | grep -oE '[0-9]+')
xpassed=${xpassed:-0}
baseline=$(cat tools/tier1_baseline.txt 2>/dev/null || echo 0)
delta=$((passed - baseline))
echo "DOTS_PASSED=$passed (baseline $baseline, delta ${delta#+})"
if [ "$passed" -lt "$baseline" ]; then
    echo "REGRESSION: tier-1 pass count dropped below the checked-in baseline"
    [ "$rc" -eq 0 ] && rc=1
fi
if [ "$xpassed" -gt 0 ]; then
    grep -a '^XPASS' "$LOG"
    if grep -a '^XPASS' "$LOG" | grep -qE \
        'test_train_step_plane_scan_matches_xla|test_train_step_pallas_backends_on_mesh'
    then
        echo "XPASS: a never-passing 8-device GSPMD divergence xfail now"
        echo "passes — the environment changed: retire all four 8-device"
        echo "xfail markers (test_plane_scan.py, test_train.py) in the same"
        echo "commit."
        [ "$rc" -eq 0 ] && rc=1
    else
        echo "XPASS: nondeterministic 8-device parity xfail(s) passed this"
        echo "run — expected on the broken partitioner, not a failure."
    fi
fi
if [ "$UPDATE_BASELINE" -eq 1 ]; then
    if [ "$rc" -eq 0 ]; then
        echo "$passed" > tools/tier1_baseline.txt
        echo "BASELINE_UPDATED: tools/tier1_baseline.txt = $passed"
    else
        echo "BASELINE_NOT_UPDATED: run was not green (rc=$rc)"
    fi
fi
exit "$rc"
