#!/usr/bin/env python
"""One-command bench conductor: the ROADMAP r06 sweep, diffed and judged.

Runs the full consolidated-measurement sweep the ROADMAP's "next TPU
window" item names — one bench.py invocation per lever, every lever
inheriting bench.py's per-variant subprocess isolation (watchdogged child
with the INIT_OK / result.json protocol), so one wedged variant can never
take the conductor down with it:

  realloop_b4        async-pipeline-fed end-to-end step (donate_batch)
  losspass_b4        loss-graph-only (fused pyramid vs elementwise tail)
  warppass_b4        both warp backends
  ssim_precision_ab  highest-vs-default SSIM matmul precision A/B
  renderpass_b4      render-only serving forward
  serve_amortize     encode-amortization curve, --mesh fleet sweep
  serve_slo          open-loop Poisson SLO knee, --mesh, trace-sampled
  aot_coldstart      cold-replica p99 store-on vs store-off
                     (bench serve_coldstart variant; reading = speedup)
  stream_session     streaming-session cadence sweep (fps + PSNR-vs-K1
                     curve; reading = frames/s at the knee cadence)

Outputs (default repo root; --smoke redirects to a temp dir so a harness
self-test never clobbers checked-in results):

  BENCH_<round>.json      schema-versioned ("mtpu-bench1") consolidated
                          record: per lever the bench JSON payload, exit
                          code, stderr tail, headline reading, the newest
                          prior reading, and a verdict
  BENCH_NOTES_<round>.md  skeleton of the promote/revert notes, one
                          section per lever with the diff pre-filled

Verdicts (printed one line per lever, recorded in the JSON): against the
newest prior BENCH_r0*.json (both this schema and the historical driver
wrapper {"n","cmd","rc","tail","parsed"} parse),

  promote   reading >= 1.05x the prior
  regress   reading <= 0.95x the prior, or the lever errored while a
            prior reading exists
  neutral   everything else — including "no prior reading" and every
            --smoke comparison (CPU smoke numbers are harness self-tests,
            never comparable to silicon priors)

Modes:
  python tools/bench_conductor.py                  # the real sweep (TPU)
  python tools/bench_conductor.py --smoke          # CPU harness self-test
  python tools/bench_conductor.py --levers a,b     # subset of the sweep
  python tools/bench_conductor.py --check-schema BENCH_r0*.json
      # validate historical + new bench JSON parseability (tier-1 gate)
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SCHEMA = "mtpu-bench1"
DEFAULT_ROUND = "r06"

# the r06 sweep (ROADMAP "one consolidated measurement sweep, then
# promote"): lever -> bench.py invocation shape
LEVERS = [
    {"name": "realloop_b4"},
    {"name": "losspass_b4"},
    {"name": "warppass_b4"},
    {"name": "ssim_precision_ab"},
    {"name": "renderpass_b4"},
    {"name": "serve_amortize", "mesh": True},
    {"name": "serve_slo", "mesh": True, "trace_sample": "0.05"},
    {"name": "aot_coldstart", "variant": "serve_coldstart"},
    {"name": "stream_session"},
    # staged-pipeline lever: the GPipe-style executor's stages x
    # microbatches sweep (bench.py pipepass_b4); the keyed ips is the
    # 1-stage x 1-microbatch point, so promote/regress reads the staged
    # step's dispatch overhead against the fused flagship directly
    {"name": "train_pipeline", "variant": "pipepass_b4"},
    # multi-host ring lever: 2 -> 3 -> 4 CPU-process hosts booted
    # zero-compile from one packed AOT artifact, aggregate views/sec +
    # remote-route fraction curve on stderr; the keyed ips is the
    # largest healthy ring's throughput.  bench builds lacking the
    # variant return the "skipped: unknown variant" string, which the
    # conductor reads as a neutral verdict
    {"name": "serve_multihost"},
    # flaky-link lever: the 2-host ring flooded through policy-armed
    # clients (serve.net.* retry/breaker/keep-alive) while faults.py
    # injects latency + every-4th drops; the keyed ips is GOODPUT (ok
    # views/s), pricing what the wire hardening holds on a lossy link.
    # Rides the same unknown-variant skip as serve_multihost on bench
    # builds predating the variant
    {"name": "serve_multihost_flaky"},
    # binary-wire lever (serve.wire.*): the 2-host ring flood swept over
    # codec json -> bin_f32 -> bin_int8 with mtpu-wire1 frames + the
    # front's owner-coalescer on the binary arms; per-codec views/s +
    # bytes/view + retry rate on stderr, keyed ips = bin_int8 views/s.
    # Rides the same unknown-variant skip on bench builds predating
    # serve.wire.*
    {"name": "serve_multihost_wire"},
]

PROMOTE_AT = 1.05
REGRESS_AT = 0.95


# ------------------------------------------------------------- lever runs

def run_lever(lever, smoke: bool, timeout_s: float):
    """One bench.py invocation for one lever; -> record dict. Variant
    isolation (child subprocess + watchdog) happens inside bench.py."""
    cmd = [sys.executable, os.path.join(REPO, "bench.py")]
    if lever.get("mesh"):
        cmd.append("--mesh")
    # a lever may alias a bench variant under a sweep-facing name
    # (aot_coldstart -> serve_coldstart); the variant keys the bench
    # payload, the lever name keys the conductor record
    variant = lever.get("variant", lever["name"])
    env = dict(os.environ, MINE_TPU_BENCH_VARIANTS=variant)
    if lever.get("trace_sample"):
        env.setdefault("MINE_TPU_BENCH_TRACE_SAMPLE", lever["trace_sample"])
    if smoke:
        env["MINE_TPU_BENCH_SMOKE"] = "1"
        env.setdefault("JAX_PLATFORMS", "cpu")
    rec = {"cmd": " ".join(cmd), "rc": None, "parsed": None, "tail": "",
           "reading": None}
    try:
        proc = subprocess.run(cmd, env=env, cwd=REPO, timeout=timeout_s,
                              capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        rec["rc"] = -1
        rec["tail"] = f"conductor timeout after {timeout_s:.0f}s"
        return rec
    rec["rc"] = proc.returncode
    rec["tail"] = "\n".join(proc.stderr.strip().splitlines()[-8:])
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                rec["parsed"] = json.loads(line)
            except ValueError:
                pass
            break
    rec["reading"] = payload_reading(rec["parsed"], variant)
    return rec


def payload_reading(parsed, lever_name):
    """Headline number for one lever from a bench.py stdout payload: the
    lever's own variants entry when numeric, else the payload value."""
    if not isinstance(parsed, dict):
        return None
    v = parsed.get("variants", {}).get(lever_name)
    if isinstance(v, (int, float)):
        return float(v)
    if isinstance(v, str):  # "error: ..." / "skipped: ..."
        return None
    val = parsed.get("value")
    return float(val) if isinstance(val, (int, float)) else None


# ------------------------------------------------------------ prior diffs

def find_prior(out_path: str, search_dir: str = REPO):
    """Newest checked-in BENCH_r<N>.json other than the one being written;
    -> (path, doc) or (None, None)."""
    best_n, best_path = -1, None
    for p in glob.glob(os.path.join(search_dir, "BENCH_r*.json")):
        if os.path.abspath(p) == os.path.abspath(out_path):
            continue
        m = re.match(r"BENCH_r(\d+)\.json$", os.path.basename(p))
        if m and int(m.group(1)) > best_n:
            best_n, best_path = int(m.group(1)), p
    if best_path is None:
        return None, None
    try:
        with open(best_path) as f:
            return best_path, json.load(f)
    except ValueError:
        return best_path, None


def prior_reading(doc, lever_name):
    """Lever reading from a prior bench JSON of EITHER shape: the
    historical driver wrapper ({"parsed": <bench payload>}) or this
    conductor's schema ({"levers": {name: {"reading"/"parsed"}}})."""
    if not isinstance(doc, dict):
        return None
    if doc.get("schema") == SCHEMA:
        rec = doc.get("levers", {}).get(lever_name)
        if isinstance(rec, dict):
            r = rec.get("reading")
            if isinstance(r, (int, float)):
                return float(r)
            return payload_reading(rec.get("parsed"), lever_name)
        return None
    # driver wrapper: the whole doc is ONE bench run, so only a numeric
    # entry for this exact lever counts — never the headline "value"
    # (r05's flagship_b4 reading is not a prior for losspass_b4)
    parsed = doc.get("parsed")
    if not isinstance(parsed, dict):
        return None
    v = parsed.get("variants", {}).get(lever_name)
    return float(v) if isinstance(v, (int, float)) else None


def judge(reading, prior, smoke: bool):
    """-> (verdict, note). See module docstring for the rules."""
    if prior is None:
        return "neutral", "no prior reading"
    if smoke:
        return "neutral", "smoke reading, not comparable to a prior"
    if reading is None:
        return "regress", "lever errored; a prior reading exists"
    ratio = reading / prior if prior else float("inf")
    if ratio >= PROMOTE_AT:
        return "promote", f"{ratio:.2f}x prior"
    if ratio <= REGRESS_AT:
        return "regress", f"{ratio:.2f}x prior"
    return "neutral", f"{ratio:.2f}x prior"


# ---------------------------------------------------------------- outputs

def render_notes(doc, prior_path):
    """BENCH_NOTES skeleton: one pre-filled section per lever, decision
    left as the TODO the next TPU window resolves."""
    rnd = doc["round"]
    lines = [f"# BENCH_NOTES_{rnd} — consolidated sweep"
             + (" (SMOKE: harness self-test, not a benchmark)"
                if doc["smoke"] else ""),
             "",
             f"Prior: {os.path.basename(prior_path) if prior_path else 'none found'}.",
             "Generated by tools/bench_conductor.py; fill each decision.",
             ""]
    for name, rec in doc["levers"].items():
        r = rec["reading"]
        p = rec["prior"]
        lines += [
            f"## {name}",
            "",
            f"* reading: {'%.3f' % r if r is not None else 'none'}"
            f" — prior: {'%.3f' % p if p is not None else 'none'}"
            f" — verdict: **{rec['verdict']}** ({rec['note']})",
            f"* rc={rec['rc']}"
            + (f" — tail: `{rec['tail'].splitlines()[-1]}`"
               if rec["tail"] else ""),
            "* decision: TODO promote / revert / hold",
            "",
        ]
    return "\n".join(lines)


# ----------------------------------------------------------- check-schema

def check_schema(paths):
    """Every bench JSON must stay parseable by prior_reading: either the
    historical driver wrapper or the mtpu-bench1 conductor schema. -> list
    of problem strings (empty = clean)."""
    problems = []
    for path in paths:
        base = os.path.basename(path)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            problems.append(f"{base}: unreadable JSON: {e}")
            continue
        if not isinstance(doc, dict):
            problems.append(f"{base}: not a JSON object")
            continue
        if doc.get("schema") == SCHEMA:
            levers = doc.get("levers")
            if not isinstance(levers, dict) or not levers:
                problems.append(f"{base}: {SCHEMA} doc without levers")
                continue
            for name, rec in levers.items():
                missing = [k for k in ("cmd", "rc", "parsed", "reading",
                                       "verdict") if k not in rec]
                if missing:
                    problems.append(
                        f"{base}: lever {name} missing {missing}")
        elif "parsed" in doc and "rc" in doc:
            p = doc["parsed"]
            if p is not None and not (isinstance(p, dict)
                                      and "variants" in p
                                      and "value" in p):
                problems.append(
                    f"{base}: driver wrapper with unparseable payload")
        else:
            problems.append(
                f"{base}: neither a {SCHEMA} doc nor a driver wrapper "
                f"(top-level keys: {sorted(doc)[:8]})")
    return problems


# ------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(
        description="one-command r06 bench sweep with prior diffs")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU harness self-test (tiny shapes; outputs go "
                         "to a temp dir unless --out is given)")
    ap.add_argument("--levers", default="",
                    help="comma-separated subset of the sweep")
    ap.add_argument("--round", default=DEFAULT_ROUND, dest="round_name")
    ap.add_argument("--out", default=None,
                    help="consolidated JSON path (default: "
                         "BENCH_<round>.json in the repo root)")
    ap.add_argument("--notes", default=None,
                    help="notes skeleton path (default: next to --out)")
    ap.add_argument("--timeout-s", type=float, default=3600.0,
                    help="conductor-side cap per lever (bench.py's own "
                         "watchdog usually fires first)")
    ap.add_argument("--check-schema", nargs="*", default=None,
                    metavar="JSON",
                    help="validate bench JSON files instead of running "
                         "(no args: every BENCH_r*.json in the repo root)")
    args = ap.parse_args(argv)

    if args.check_schema is not None:
        paths = args.check_schema or sorted(
            glob.glob(os.path.join(REPO, "BENCH_r*.json")))
        if not paths:
            print("check-schema: no bench JSON files to check",
                  file=sys.stderr)
            return 0
        problems = check_schema(paths)
        for p in problems:
            print(f"check-schema: {p}", file=sys.stderr)
        if problems:
            return 1
        print(f"check-schema: {len(paths)} file(s) OK "
              f"({', '.join(os.path.basename(p) for p in paths)})")
        return 0

    known = [lv["name"] for lv in LEVERS]
    wanted = [n for n in args.levers.split(",") if n] or known
    unknown = [n for n in wanted if n not in known]
    if unknown:
        print(f"unknown lever(s): {', '.join(unknown)} "
              f"(have: {', '.join(known)})", file=sys.stderr)
        return 2
    sweep = [lv for lv in LEVERS if lv["name"] in wanted]

    out = args.out
    if out is None:
        out_dir = tempfile.mkdtemp(prefix="bench_smoke_") if args.smoke \
            else REPO
        out = os.path.join(out_dir, f"BENCH_{args.round_name}.json")
    notes = args.notes or os.path.join(
        os.path.dirname(out), f"BENCH_NOTES_{args.round_name}.md")

    prior_path, prior_doc = find_prior(out)
    doc = {"schema": SCHEMA, "round": args.round_name,
           "smoke": bool(args.smoke),
           "prior": os.path.basename(prior_path) if prior_path else None,
           "levers": {}}
    for lever in sweep:
        name = lever["name"]
        print(f"lever {name}: running ...", flush=True)
        rec = run_lever(lever, args.smoke, args.timeout_s)
        rec["prior"] = prior_reading(prior_doc, name)
        rec["verdict"], rec["note"] = judge(rec["reading"], rec["prior"],
                                            args.smoke)
        doc["levers"][name] = rec
        r = rec["reading"]
        p = rec["prior"]
        print(f"lever {name}: reading="
              f"{'%.3f' % r if r is not None else 'none'} prior="
              f"{'%.3f' % p if p is not None else 'none'} -> "
              f"{rec['verdict']} ({rec['note']})", flush=True)

    tmp = out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, out)
    with open(notes, "w") as f:
        f.write(render_notes(doc, prior_path))
    print(f"wrote {out}")
    print(f"wrote {notes}")
    errored = [n for n, rec in doc["levers"].items()
               if rec["rc"] != 0 or rec["parsed"] is None]
    if errored:
        print(f"levers with errors: {', '.join(errored)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
