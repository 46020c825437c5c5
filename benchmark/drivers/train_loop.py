"""Traffic kind `train_loop`: the train step fed by the real loop.

The program's own objects, wired as train_cli.py and TrainLoop wire them:
`SynthesisTrainer` (on the mesh train_cli builds on that many devices),
`dataset.batch_iterator(workers=...)` -> `DeviceStager` -> the donated
jitted step, epochs chained without a pause, a device sync at the loop's log
cadence and nowhere else. The idea is bench.py's `_measure_realloop`; the
loop is written out here because the benchmark may lean only on the
program's public objects, not on TrainLoop's private methods.

Everything a later cell may vary is data in its traffic file:
  dataset        {"num_views", "num_points"} of the synthetic scene
  config_overrides  any key of the repo's config space (mpi.*, data.*,
                 parallel.*, training.*), over the configuration's own
  warmup         steps run before and after one change of epoch
  trace_seconds  length of the profiler window in a traced run
"""

from __future__ import annotations

import itertools
import statistics
import time

from benchmark import harness, program

PALLAS_BACKENDS = ("pallas", "pallas_diff", "pallas_sep", "pallas_fused")
STEP_PROGRAM = "_train_step_impl"   # the jitted step's name in the compiler


class Feed:
    """Staged batches for ever: one `batch_iterator` + `DeviceStager` per
    epoch, the next epoch's opened when the last one's ends, as
    `TrainLoop.run` chains `train_epoch` calls."""

    def __init__(self, dataset, trainer, config, seed, epoch, offset):
        self.dataset, self.trainer = dataset, trainer
        self.seed = seed
        self.num_workers = int(config.get("data.num_workers", 0) or 0)
        self.prefetch_batches = max(1, int(
            config.get("data.prefetch_batches", 2)))
        self.staging_buffers = int(config.get("data.staging_buffers", 2))
        self.epoch = epoch
        self.step_in_epoch = 0
        self._open(offset)

    def _open(self, offset):
        from mine_tpu.data.pipeline import DeviceStager
        host = self.dataset.batch_iterator(
            batch_size=self.trainer.local_batch_size(), shuffle=True,
            seed=self.seed, epoch=self.epoch, drop_last=True, shard_index=0,
            num_shards=1, workers=self.num_workers,
            prefetch_batches=self.prefetch_batches)
        if offset:
            host = itertools.islice(host, offset, None)
        # staging_buffers <= 1 is the loop's synchronous A/B path: a stager
        # of depth 1 is the same copy, made one batch ahead
        self._staged = iter(DeviceStager(host, self.trainer.put_batch,
                                         depth=max(1, self.staging_buffers)))
        self.step_in_epoch = offset

    def close(self, timeout=20.0):
        import threading
        staged, self._staged = self._staged, iter(())
        staged.close()  # data/pipeline.prefetch's finally stops its producer
        deadline = time.monotonic() + timeout
        for t in threading.enumerate():
            if t.name.startswith(("mine-tpu-prefetch", "mine-tpu-assembler")):
                t.join(max(0.0, deadline - time.monotonic()))
                if t.is_alive():
                    raise harness.BenchError("feed thread %s did not stop"
                                             % t.name)

    def next(self):
        while True:
            try:
                sb = next(self._staged)
            except StopIteration:
                self.epoch += 1
                self._open(0)
                continue
            self.step_in_epoch += 1
            return sb.batch


def setup(cell, seed, devices, spans):
    import jax

    from mine_tpu.data.synthetic import SyntheticPairDataset
    from mine_tpu.parallel.mesh import DATA_AXIS, make_mesh
    from mine_tpu.train.step import SynthesisTrainer

    config = cell.program_config()
    wl = cell.workload
    use = list(devices[:cell.chips])
    plane = int(config.get("parallel.plane_parallel", 1))
    data = int(config.get("parallel.data_parallel", -1))
    mesh = None
    if len(use) > 1 or plane > 1:   # as train_cli.py decides
        mesh = make_mesh(data=data, plane=plane, devices=use)
        harness.say("mesh: %s" % (mesh,))
    ds_cfg = wl["dataset"]
    if ds_cfg.get("kind", "synthetic_pairs") != "synthetic_pairs":
        raise harness.BenchError("this driver feeds synthetic_pairs, not %r"
                                 % ds_cfg["kind"])
    t0 = time.perf_counter()
    dataset = SyntheticPairDataset(
        num_views=int(ds_cfg["num_views"]),
        num_points=int(ds_cfg.get(
            "num_points", config.get("data.visible_point_count", 256))),
        height=int(config["data.img_h"]), width=int(config["data.img_w"]),
        seed=harness.mix_seed(seed, "scene"))
    harness.say("dataset: %d pairs at %dx%d in %.1fs" % (
        len(dataset), config["data.img_h"], config["data.img_w"],
        time.perf_counter() - t0))
    data_size = mesh.shape[DATA_AXIS] if mesh is not None else 1
    global_batch = int(config["data.per_gpu_batch_size"]) * data_size
    steps_per_epoch = max(1, len(dataset) // global_batch)
    trainer = SynthesisTrainer(config, mesh=mesh,
                               steps_per_epoch=steps_per_epoch)
    harness.say("backends: warp=%s composite=%s; global batch %d, %d steps "
                "an epoch" % (trainer.cfg.warp_backend,
                              trainer.cfg.composite_backend, global_batch,
                              steps_per_epoch))
    t0 = time.perf_counter()
    state = program.seeded_state(trainer, trainer.global_batch_size(),
                                 harness.mix_seed(seed, "weights"))
    jax.block_until_ready(state.step)
    harness.say("init_state in %.1fs" % (time.perf_counter() - t0))

    ctx = {"cell": cell, "config": config, "trainer": trainer,
           "devices": use, "spans": spans, "state": state,
           "global_batch": global_batch, "steps_per_epoch": steps_per_epoch,
           "log_interval": max(1, min(int(config.get(
               "training.log_interval", 10)), steps_per_epoch)),
           "metrics": [], "temp_bytes": 0}

    # warm-up: the last steps of epoch 1 and the first of epoch 2, so the
    # first step's compile and whatever a change of epoch starts lazily
    # land here and not in the window
    warm = wl.get("warmup", {})
    before = min(int(warm.get("steps_before_epoch_end", 2)), steps_per_epoch)
    after = int(warm.get("steps_after_epoch_start", 2))
    ctx["feed"] = Feed(dataset, trainer, config,
                       harness.mix_seed(seed, "order"), epoch=1,
                       offset=steps_per_epoch - before)
    t0 = time.perf_counter()
    first = ctx["feed"].next()
    ctx["temp_bytes"] = _step_temp_bytes(trainer, state, first)
    harness.say("step program temp %.2f GiB by memory_analysis (%.1fs)"
                % (ctx["temp_bytes"] / 2**30, time.perf_counter() - t0))
    t0 = time.perf_counter()
    _step(ctx, first)
    jax.block_until_ready(ctx["metrics"][-1])
    harness.say("first step (compile or cache load + run) in %.1fs"
                % (time.perf_counter() - t0))
    for _ in range(before - 1 + after):
        _step(ctx, ctx["feed"].next())
    jax.block_until_ready((ctx["state"].step, ctx["metrics"][-1]))
    return ctx


def _step_temp_bytes(trainer, state, batch) -> int:
    """Scratch the step program needs beside its arguments, from the
    compiler's memory analysis (the allocator's peak does not count it on
    this backend). The lowering shares the jit's trace; the compile is the
    one the first step would make, and lands in the same caches."""
    try:
        analysis = trainer._train_step.lower(state, batch).compile() \
            .memory_analysis()
        return int(getattr(analysis, "temp_size_in_bytes", 0) or 0)
    except Exception as e:  # noqa: BLE001 - a missing analysis is not a fault
        harness.say("no memory analysis of the step: %r" % (e,))
        return 0


def _step(ctx, batch):
    ctx["state"], metrics = ctx["trainer"].train_step(ctx["state"], batch)
    # stays on the device: fetched after the window closes
    ctx["metrics"].append({k: metrics[k] for k in (
        "loss", "skipped_steps", "warp_fallback_frac") if k in metrics})


def measure(ctx, seconds, tracer, watch):
    import jax
    spans, feed = ctx["spans"], ctx["feed"]
    spe, log_interval = ctx["steps_per_epoch"], ctx["log_interval"]
    first_index = len(ctx["metrics"])
    spans.recording = True
    wall0 = time.time()
    t0 = time.perf_counter()
    if tracer is not None:
        tracer.start_after(0.3 * seconds)
    steps = 0
    while True:
        with spans.span("feed.next"):
            batch = feed.next()
        with spans.span("step.dispatch"):
            _step(ctx, batch)
        steps += 1
        if feed.step_in_epoch % log_interval == 0:
            # the loop reads its metrics here: the one sync it makes
            with spans.span("loop.log_sync"):
                jax.block_until_ready(ctx["metrics"][-1])
        if time.perf_counter() - t0 >= seconds:
            break
    with spans.span("window.sync"):
        jax.block_until_ready((ctx["state"].step, ctx["metrics"][-1]))
    window_s = time.perf_counter() - t0
    wall1 = time.time()
    spans.recording = False
    if tracer is not None:
        tracer.join()

    # ---- after the window: read back, check ----
    fetched = jax.device_get(ctx["metrics"])
    losses = [float(m["loss"]) for m in fetched]
    skipped = int(fetched[-1].get("skipped_steps", 0))
    fallback = [float(m["warp_fallback_frac"]) for m in fetched
                if "warp_fallback_frac" in m]
    in_window = watch.between(wall0, wall1)
    tcfg = ctx["trainer"].cfg
    pallas = (tcfg.warp_backend in PALLAS_BACKENDS
              and tcfg.composite_backend in PALLAS_BACKENDS)
    tail = statistics.median(losses[-5:])
    checks = {
        "losses_finite": all(x == x and abs(x) != float("inf")
                             for x in losses),
        "no_skipped_steps": skipped == 0,
        "loss_fell": tail < losses[0],
        "no_compile_in_window": not in_window,
        # on the chip the Pallas kernels are the path under test; the CPU
        # rehearsal of the tests resolves `auto` to the XLA ops
        "pallas_backends": pallas or harness.REQUIRED_PLATFORM != "tpu",
        "pallas_path_taken": (not pallas) or (
            bool(fallback) and max(fallback) < 1.0),
    }
    harness.say("losses: warm-up %s; window first %s last %s; median of "
                "last five %.4f" % (
                    [round(x, 4) for x in losses[:first_index]],
                    [round(x, 4) for x in losses[first_index:first_index + 3]],
                    [round(x, 4) for x in losses[-3:]], tail))
    harness.say("skipped_steps %d; warp_fallback_frac mean %.4f max %.4f; "
                "compile requests in window: %s" % (
                    skipped, sum(fallback) / max(len(fallback), 1),
                    max(fallback or [0.0]), in_window))
    harness.say("checks: %s" % checks)
    step_cache = watch.summary(STEP_PROGRAM)
    harness.say("train step program: persistent-cache hits %d, misses %d"
                % (step_cache["hits"], step_cache["misses"]))
    images = steps * ctx["global_batch"]
    return {
        "window_start": wall0, "window_s": window_s,
        "attempted": steps, "failed": skipped,
        "correct": all(checks.values()), "checks": checks,
        "end_to_end": {"train_images_per_s": images / window_s},
        "counters": {"steps": steps, "images": images,
                     "global_batch": ctx["global_batch"],
                     "steps_per_epoch": spe,
                     "warp_fallback_mean": (sum(fallback)
                                            / max(len(fallback), 1)),
                     "step_program": STEP_PROGRAM},
        "shapes": _shapes(ctx),
        "temp_bytes": ctx["temp_bytes"],
    }


def _shapes(ctx):
    """What benchmark/roofline.py prices the Pallas calls of one step from."""
    cfg = ctx["trainer"].cfg
    return {"kind": "train", "batch_per_device": ctx["global_batch"]
            // max(len(ctx["devices"]), 1),
            "planes": cfg.num_bins_total, "height": cfg.img_h,
            "width": cfg.img_w, "scales": 4 if ctx["config"].get(
                "training.use_multi_scale", True) else 1,
            "band": int(ctx["config"].get("training.warp_band", 48)),
            "warp_dtype": str(ctx["config"].get("training.warp_dtype",
                                                "float32"))}


def teardown(ctx):
    """Stop the feed's threads and wait for them: a stager thread still
    inside a device copy when the interpreter shuts down aborts the process
    (chip run, PR 26: SIGABRT after the result line)."""
    ctx["feed"].close()
