#!/usr/bin/env python
"""Chip smoke: the quickest proof that the system still starts on the chip.

  python chip_smoke.py            # one chip: train, then serve
  python chip_smoke.py --chips 4  # four chips: the mesh step vs one chip

Drives the repo's two programs the way a user does — `python train_cli.py`
and `python serve_cli.py` as child processes, one after the other — on
`mine_tpu/configs/params_llff.yaml` as shipped (384x512, N=32, ResNet-50,
bf16, per-chip batch 2), with the synthetic dataset and a handful of steps
as the only changes. This parent never imports JAX: a chip belongs to one
process, so it learns the device from what the children log. The children
get JAX_PLATFORMS=tpu, which makes a missing chip an error in JAX itself.

Everything read here is something the CLIs log for any user; the compiler
lines come from JAX_LOG_COMPILES, the lowered programs from
JAX_DUMP_IR_TO and (four chips) the optimized HLO from --xla_dump_to.
Output lands in chip_smoke_out/ (git-ignored). The last line of stdout is
the result, printed only when every check passed; any failure exits 1.
"""

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chip_smoke_out")
CONFIG = os.path.join("mine_tpu", "configs", "params_llff.yaml")

# what the children are held to; the CPU rehearsal among the tests
# (tests/test_chip_smoke.py) swaps these, the script has no option for it
PLATFORM = "tpu"
SEED = 0
# 5 synthetic pairs / batch 2 = 2 steps per epoch -> 8 optimizer steps
TRAIN_EXTRA = {"data.name": "synthetic", "training.epochs": 4,
               "training.log_interval": 1, "training.seed": SEED}
N_IMAGES = 2            # distinct images; the first is requested twice
PALLAS_BACKENDS = ("pallas", "pallas_diff")
KERNEL_CALL = "tpu_custom_call"   # what a compiled Pallas kernel lowers to
TIMEOUT_S = {"train": 700, "serve": 420, "mesh": 900, "one_chip": 900}
# --chips 4: first-step loss of the 2x2 mesh against one chip, same seed
# and global batch. Per-example conv math does not depend on how the batch
# is split; what differs is the order of the f32 cross-device sums (BN
# statistics, loss means), which moves some bf16 activations by one ulp
# (2^-8 = 0.4%), averaged over ~1e6 loss terms. Expected ~1e-3 relative;
# held to one percent.
MESH_LOSS_RTOL = 1e-2
# one chip of a four-chip host, for the comparison run (libtpu's own knobs)
ONE_CHIP_ENV = {"TPU_VISIBLE_CHIPS": "0", "TPU_VISIBLE_DEVICES": "0",
                "TPU_PROCESS_BOUNDS": "1,1,1",
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1"}


class SmokeFailure(Exception):
    pass


def say(msg):
    print("[smoke +%6.1fs] %s" % (time.monotonic() - _T0, msg), flush=True)


_T0 = time.monotonic()


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


def child_env(name, extra=None):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS=PLATFORM, JAX_LOG_COMPILES="1",
               JAX_DUMP_IR_TO=os.path.join(OUT, name + "_ir"),
               JAX_DUMP_IR_MODES="stablehlo", PYTHONUNBUFFERED="1")
    env.update(extra or {})
    return env


def run_child(name, argv, env):
    """Run one CLI as a user would; stdout+stderr to OUT/<name>.log.
    Returns the log text. Raises on a non-zero exit or the phase timeout
    (the child is killed; nothing this script started is left running)."""
    log_path = os.path.join(OUT, name + ".log")
    say("%s: %s" % (name, " ".join(argv)))
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen([sys.executable] + argv, cwd=ROOT, env=env,
                                stdout=log, stderr=subprocess.STDOUT)
        try:
            rc = proc.wait(timeout=TIMEOUT_S[name])
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            rc = "timeout after %ds" % TIMEOUT_S[name]
    with open(log_path, errors="replace") as f:
        text = f.read()
    say("%s: exit %s after %.1fs" % (name, rc, time.monotonic() - t0))
    if rc != 0:
        sys.stderr.write("".join(text.splitlines(True)[-40:]))
        raise SmokeFailure("%s exited %s (log: %s)" % (name, rc, log_path))
    return text


# ---------------- what the CLIs log ----------------

def runtime_of(text, name):
    m = re.search(r"Runtime: (\{.*\})", text)
    check(m, "%s logged no Runtime line" % name)
    rt = json.loads(m.group(1))
    say("%s runtime: %s" % (name, json.dumps(rt)))
    check(rt["platform"] == PLATFORM,
          "%s ran on platform %r, not %r" % (name, rt["platform"], PLATFORM))
    return rt


def backends_of(text, name):
    m = re.search(r"Backends: (.*)", text)
    check(m, "%s logged no Backends line" % name)
    backends = dict(kv.split("=") for kv in m.group(1).split())
    say("%s backends: %s" % (name, backends))
    return backends


def compile_seconds(text, fn_name):
    """Cold compile seconds of every program named `fn_name`
    (JAX_LOG_COMPILES), and how many came from the persistent cache."""
    # (jax logs each line through two handlers: keep one of each)
    secs = [float(s) for s in dict.fromkeys(re.findall(
        r"Finished XLA compilation of jit\(%s\) in ([0-9.e+-]+) sec"
        % re.escape(fn_name), text))]
    hits = len(re.findall(
        r"Persistent compilation cache hit for 'jit_%s'" % re.escape(fn_name),
        text))
    return secs, hits


def lowered_holds_kernel(name, fn_name):
    """Does a lowered `fn_name` program (JAX_DUMP_IR_TO) call a kernel?"""
    ir_dir = os.path.join(OUT, name + "_ir")
    files = [f for f in (os.listdir(ir_dir) if os.path.isdir(ir_dir) else [])
             if fn_name in f]
    check(files, "%s: no lowered %s program was dumped" % (name, fn_name))
    counts = []
    for f in files:
        with open(os.path.join(ir_dir, f), errors="replace") as fh:
            counts.append(fh.read().count(KERNEL_CALL))
    say("%s: %s in lowered %s: %s" % (name, KERNEL_CALL, fn_name, counts))
    return max(counts) > 0


def train_steps(text):
    """[(gstep, loss, {diag})] from the per-step log lines."""
    steps = []
    for m in re.finditer(r"global_step = (\d+) total_loss = (\S+)", text):
        steps.append([int(m.group(1)), float(m.group(2)), {}])
    diags = re.findall(r"diag: (.*)", text)
    for step, d in zip(steps, diags):
        step[2] = {k: float(v) for k, v in
                   re.findall(r"(\w+) = (\S+)", d)}
    return steps


def check_train(name, text):
    rt = runtime_of(text, name)
    backends = backends_of(text, name)
    steps = train_steps(text)
    losses = [s[1] for s in steps]
    say("%s losses: %s" % (name, losses))
    secs, hits = compile_seconds(text, "_train_step_impl")
    say("%s train step: cold compile %s s, persistent-cache hits %d"
        % (name, [round(s, 1) for s in secs], hits))
    check(len(steps) >= 3, "%s took %d optimizer steps, need 3" %
          (name, len(steps)))
    check(all(x == x and abs(x) != float("inf") for x in losses),
          "%s: non-finite loss in %s" % (name, losses))
    skipped = [s[2].get("skipped_steps") for s in steps]
    fallback = [s[2].get("warp_fallback_frac") for s in steps]
    say("%s skipped_steps: %s warp_fallback_frac: %s"
        % (name, skipped, fallback))
    check(all(s == 0 for s in skipped),
          "%s: the non-finite guard skipped steps (%s)" % (name, skipped))
    check(losses[-1] < losses[0], "%s: loss did not decrease (%s -> %s)"
          % (name, losses[0], losses[-1]))
    check(backends.get("warp") in PALLAS_BACKENDS
          and backends.get("composite") in PALLAS_BACKENDS,
          "%s resolved non-Pallas backends %s" % (name, backends))
    check(all(f is not None for f in fallback) and min(fallback) < 1.0,
          "%s: every step sent every plane to the gather fallback "
          "(warp_fallback_frac %s)" % (name, fallback))
    check(lowered_holds_kernel(name, "_train_step_impl"),
          "%s: lowered train step holds no %s" % (name, KERNEL_CALL))
    return rt, steps


def train_argv(version, extra, more=()):
    return ["train_cli.py", "--config_path", CONFIG,
            "--workspace", os.path.join(OUT, "ws"), "--version", version,
            "--extra_config", json.dumps(extra)] + list(more)


# ---------------- one chip: train, then serve ----------------

def write_images(img_dir):
    """N_IMAGES smooth random photos from SEED, and a byte-identical copy
    of the first under a later name: the repeated request."""
    import numpy as np
    from PIL import Image
    os.makedirs(img_dir)
    rng = np.random.RandomState(SEED)
    for i in range(N_IMAGES):
        coarse = rng.uniform(0, 255, (12, 16, 3)).astype(np.uint8)
        img = Image.fromarray(coarse).resize((512, 384), Image.BICUBIC)
        img.save(os.path.join(img_dir, "img%d.png" % i))
    shutil.copy(os.path.join(img_dir, "img0.png"),
                os.path.join(img_dir, "img%d_again.png" % N_IMAGES))


def phase_train():
    text = run_child("train", train_argv("smoke", TRAIN_EXTRA),
                     child_env("train"))
    rt, _ = check_train("train", text)
    ckpt = os.path.join(OUT, "ws", "smoke", "checkpoint_latest")
    check(os.path.exists(ckpt), "train wrote no %s" % ckpt)
    return rt, ckpt


def phase_serve(ckpt):
    img_dir = os.path.join(OUT, "images")
    write_images(img_dir)
    text = run_child("serve", [
        "serve_cli.py", "--checkpoint_path", ckpt, "--data_path", img_dir,
        "--output_dir", os.path.join(OUT, "serve")], child_env("serve"))
    rt = runtime_of(text, "serve")
    backends = backends_of(text, "serve")
    secs, hits = compile_seconds(text, "_render_impl")
    say("serve render programs: cold compile %s s, persistent-cache hits %d"
        % ([round(s, 1) for s in secs], hits))
    with open(os.path.join(OUT, "serve", "events.jsonl")) as f:
        for ev in map(json.loads, f):
            if ev.get("kind") == "serve.bucket_compile":
                # first call of a bucket: trace + compile (or cache load)
                # + one render, as the engine times it
                say("serve bucket entries=%d poses=%d warp=%s dtype=%s: "
                    "first call %.1f s" % (
                        ev["entries_bucket"], ev["poses_bucket"],
                        ev["warp_impl"], ev["dtype"],
                        ev["compile_ms"] / 1e3))
    encodes = re.findall(r"image (\S+): id=(\w+) encode=(\w+)", text)
    say("serve images: %s" % encodes)
    check(len(encodes) == N_IMAGES + 1, "serve saw %d images, expected %d"
          % (len(encodes), N_IMAGES + 1))
    check(encodes[-1][2] == "cached" and encodes[-1][1] == encodes[0][1],
          "the repeated image did not hit the cache: %s" % (encodes,))
    views = re.findall(r"views (\S+): warp=(\w+) n=(\d+) finite=(\w+) "
                       r"rgb_min=(\S+) rgb_max=(\S+) rgb_std=(\S+)", text)
    for v in views:
        say("serve views %s: warp=%s n=%s finite=%s min=%s max=%s std=%s" % v)
    check(views, "serve rendered no views")
    check(all(v[3] == "True" for v in views), "non-finite rendered views")
    check(all(float(v[6]) > 0 and float(v[5]) > float(v[4]) for v in views),
          "a rendered trajectory is all one value")
    m = re.search(r"serve stats: (.*)", text)
    check(m, "serve logged no stats line")
    stats = dict(kv.split("=") for kv in m.group(1).split())
    say("serve stats: %s" % m.group(1))
    check(int(stats["hits"]) >= 1, "no cache hit in %s" % m.group(1))
    check(int(stats["load_errors"]) == 0, "AOT store load errors")
    m = re.search(r"rendered (\d+) views from (\d+) images", text)
    check(m and int(m.group(1)) > 0, "serve rendered nothing")
    say("serve: %s views from %s images" % m.groups())
    check(backends.get("composite") in PALLAS_BACKENDS,
          "serve resolved a non-Pallas composite: %s" % backends)
    check(lowered_holds_kernel("serve", "_render_impl"),
          "serve: lowered render program holds no %s" % KERNEL_CALL)
    return rt


def one_chip():
    rt, ckpt = phase_train()   # the train child has exited before serve starts
    rt_serve = phase_serve(ckpt)
    check(rt_serve["kind"] == rt["kind"], "train and serve saw other devices")
    return rt


# ---------------- four chips: the mesh step and its comparison ----------------

def collectives_in(dump_dir):
    """Collective ops in the optimized HLO of the mesh train step."""
    files = [f for f in (os.listdir(dump_dir) if os.path.isdir(dump_dir)
                         else []) if f.endswith("after_optimizations.txt")]
    check(files, "no optimized HLO of the mesh step in %s (loaded from the "
          "compile cache? remove it and run again)" % dump_dir)
    with open(os.path.join(dump_dir, max(
            files, key=lambda f: os.path.getsize(
                os.path.join(dump_dir, f)))), errors="replace") as fh:
        hlo = fh.read()
    return {op: len(re.findall(r" %s(?:-start)?\(" % op, hlo))
            for op in ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")}


def four_chips():
    extra = dict(TRAIN_EXTRA, **{"training.epochs": 2})
    dump = os.path.join(OUT, "mesh_hlo")
    xla_flags = (os.environ.get("XLA_FLAGS", "") + " --xla_dump_to=" + dump +
                 " --xla_dump_hlo_as_text"
                 " --xla_dump_hlo_module_re=.*_train_step_impl.*").strip()
    text = run_child(
        "mesh", train_argv("mesh", dict(extra, **{
            "data.per_gpu_batch_size": 1}), ["--plane_parallel", "2"]),
        child_env("mesh", {"XLA_FLAGS": xla_flags}))
    rt, mesh_steps = check_train("mesh", text)
    check(rt["count"] == 4, "mesh run saw %d devices, not 4" % rt["count"])
    m = re.search(r"Mesh: (.*)", text)
    check(m and "'data': 2" in m.group(1) and "'plane': 2" in m.group(1),
          "mesh is not data 2 x plane 2: %s" % (m and m.group(1)))
    say("mesh: %s" % m.group(1))
    m = re.search(r"Param placement: (\{.*\})", text)
    check(m, "mesh run logged no Param placement line")
    place = json.loads(m.group(1))
    say("mesh param placement: %s" % place)
    check(place["devices"] == 4 and place["replicated"],
          "parameters are not replicated over the 4 devices: %s" % place)
    m = re.search(r"Device memory: (\[.*\])", text)
    check(m, "mesh run logged no Device memory line")
    mem = json.loads(m.group(1))
    say("mesh per-device memory: %s" % mem)
    if PLATFORM == "tpu":  # the CPU backend reports no memory stats
        # every device holds its replica of the parameters and Adam state
        # (the allocator's peak does not count a program's scratch on this
        # backend, so activations do not show here; the collectives below
        # are the evidence that the step itself is partitioned)
        held = [d["bytes_in_use"] for d in mem]
        check(len(held) == 4 and min(held) > 0.5 * max(held) > 0,
              "the state is not spread over the 4 devices: %s" % mem)
    colls = collectives_in(dump)
    say("mesh step collectives (optimized HLO): %s" % colls)
    check(colls["all-reduce"] > 0, "mesh step holds no all-reduce")
    check(colls["all-gather"] + colls["all-to-all"]
          + colls["collective-permute"] > 0,
          "mesh step moves nothing along the plane axis: %s" % colls)

    text = run_child("one_chip", train_argv("one_chip", extra),
                     child_env("one_chip", ONE_CHIP_ENV))
    rt1, one_steps = check_train("one_chip", text)
    check(rt1["count"] == 1, "comparison run saw %d devices" % rt1["count"])
    a, b = mesh_steps[0][1], one_steps[0][1]
    rel = abs(a - b) / abs(b)
    say("first-step loss: mesh %.6f, one chip %.6f, relative difference "
        "%.3e (tolerance %.0e)" % (a, b, rel, MESH_LOSS_RTOL))
    say("per-step losses: mesh %s, one chip %s"
        % ([s[1] for s in mesh_steps], [s[1] for s in one_steps]))
    check(rel <= MESH_LOSS_RTOL, "mesh and one-chip first-step losses "
          "differ by %.3e > %.0e" % (rel, MESH_LOSS_RTOL))
    return rt


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh step and the "
                         "one-chip run it is compared with")
    args = ap.parse_args(argv)
    for cli in ("train_cli.py", "serve_cli.py", CONFIG):
        if not os.path.exists(os.path.join(ROOT, cli)):
            sys.stderr.write("chip_smoke: %s not found next to this script\n"
                             % cli)
            return 2
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    say("compile cache: %s" % (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                               or os.path.join(ROOT, ".jax_cache")))
    say("native image decoder (mine_tpu/native/libmtio.so): %s; the "
        "synthetic data and serve_cli's cv2.imread use neither it nor PIL"
        % ("built" if os.path.exists(os.path.join(
            ROOT, "mine_tpu", "native", "libmtio.so")) else
           "not built, loaders would take the PIL path"))
    try:
        rt = four_chips() if args.chips == 4 else one_chip()
        check(rt["count"] == args.chips,
              "ran on %d device(s), not %d" % (rt["count"], args.chips))
    except SmokeFailure as e:
        sys.stderr.write("chip_smoke FAILED: %s\n" % e)
        return 1
    assert "jax" not in sys.modules  # the parent never holds the chip
    say("all checks passed in %.0fs" % (time.monotonic() - _T0))
    print(json.dumps({"ok": True, "device": {
        "platform": rt["platform"], "kind": rt["kind"],
        "count": rt["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
