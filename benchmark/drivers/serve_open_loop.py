"""Traffic kind `serve_open_loop`: the render-only serving call under an open
loop of single-view requests at a rate fixed in the traffic file.

The program's own objects, wired as serve_cli.py and ServeFleet wire them:
one `RenderEngine` + `MPICache` (serve.* keys), every image encoded once
through `VideoGenerator` (the engine's encode path: one jitted program shared
by all images), requests through `ContinuousBatcher.submit`. The arithmetic
is bench.py's `_measure_serve_slo` (open loop, latency from the SCHEDULED
arrival); what it had wrong is not copied: one cached entry, 64 requests, the
legacy scheduler, a rate re-calibrated inside every run.

Everything a later cell may vary is data in its traffic file:
  images, zipf_exponent     the resident set and its popularity
  rate_views_per_s          the offered rate, a number (found once by
                            benchmark/sweep.py; frozen)
  resident_at_start         true: every image is encoded in set-up; false:
                            the cache starts empty and requests carry their
                            pixels (encodes inside the window)
  engine.warp_impl          the engine's warp backend
  config_overrides          serve.* keys (cache_bytes, cache_quant,
                            max_bucket, max_requests, max_wait_ms, ...)
  reference_views           how many served views are compared with
                            benchmark/reference.py after the window

Every seed gives the same work in another order: the same multiset of
inter-arrival gaps (the quantiles of the exponential distribution at the
rate, so a Poisson process with its count fixed), the same count of requests
per popularity rank and per pose, permuted by the seed.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import harness, program

# Served view against the plain float32 reference on the same dequantized
# planes and pose: mean absolute error over the rgb image (values in [0, 1]).
# On the CPU, and through the Pallas kernels in interpret mode, the program
# agrees with the reference to 1e-6. On the v5e it differs by 2.6e-3 to
# 1.23e-2 (56 views, 7 seeds, PR 26): the program's float32 matmuls and
# einsums (the 3x3 homographies, the pixel-coordinate map, the kernels' tent
# weights) run at the TPU's DEFAULT matmul precision, one bfloat16 pass,
# which rounds pixel coordinates above 256 to even numbers; on the smooth
# test photos (up to ~1% of the value range per pixel) a sample taken up to
# one pixel off is an error of this size. Held to 2e-2, 1.6 x the largest
# seen. That fails a wrong plane order (7.3e-2 to 8.8e-2, measured on the
# reference itself in every run and required to be at least twice the
# error), a wrong image or a wrong pose; it cannot see one dropped plane
# (2e-4 to 1e-3 with random weights, printed for the record). PERF.md lists
# the precision for a later PR, after which a `benchmark` PR can tighten this.
REFERENCE_MEAN_ABS_TOL = 2e-2
REFERENCE_FRACTION_OF_FAULT = 0.5
DRAIN_TIMEOUT_S = 60.0


# ---------------- traffic from the seed ----------------

def schedule(wl, seed, seconds, n_images, n_poses):
    """Arrival times, image ranks and pose indices of one window."""
    rate = float(wl["rate_views_per_s"])
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= (seconds - 0.5 / rate) / gaps.sum()
    rng = np.random.RandomState(harness.mix_seed(seed, "arrivals"))
    t = np.cumsum(rng.permutation(gaps))
    # requests per popularity rank: Zipf, by largest remainder
    weights = 1.0 / np.arange(1, n_images + 1) ** float(
        wl.get("zipf_exponent", 1.0))
    share = weights / weights.sum() * n
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts))[:n - counts.sum()]:
        counts[i] += 1
    ranks = np.repeat(np.arange(n_images), counts)
    rank_to_image = np.random.RandomState(
        harness.mix_seed(seed, "popularity")).permutation(n_images)
    order = np.random.RandomState(harness.mix_seed(seed, "order"))
    images = rank_to_image[order.permutation(ranks)]
    poses = order.permutation(np.arange(n) % n_poses)
    return {"t": [float(x) for x in t], "image": [int(x) for x in images],
            "pose": [int(x) for x in poses]}


def make_images(n, height, width, seed):
    """Smooth random photos as chip_smoke.py makes them: a coarse random
    grid, upsampled bicubically. uint8 [H,W,3]."""
    import cv2
    rng = np.random.RandomState(harness.mix_seed(seed, "images"))
    out = []
    for _ in range(n):
        coarse = rng.uniform(0, 255, (12, 16, 3)).astype(np.uint8)
        out.append(cv2.resize(coarse, (width, height),
                              interpolation=cv2.INTER_CUBIC))
    return out


# ---------------- set-up ----------------

def setup(cell, seed, devices, spans):
    import jax

    from mine_tpu.config import serve_config_from_dict
    from mine_tpu.infer.video import (WARP_BAND, VideoGenerator,
                                      generate_trajectories)
    from mine_tpu.kernels import on_tpu_backend
    from mine_tpu.serve import ContinuousBatcher, MPICache, RenderEngine
    from mine_tpu.train.step import SynthesisTrainer

    config = cell.program_config()
    wl = cell.workload
    serve_cfg = serve_config_from_dict(config)
    H, W = int(config["data.img_h"]), int(config["data.img_w"])

    t0 = time.perf_counter()
    trainer = SynthesisTrainer(config, steps_per_epoch=1)
    state = program.seeded_state(trainer, 1,
                                 harness.mix_seed(seed, "weights"))
    params, batch_stats = state.params, state.batch_stats
    jax.block_until_ready(state.step)
    del state
    harness.say("init_state in %.1fs" % (time.perf_counter() - t0))

    backend = "pallas" if on_tpu_backend() else "xla"
    warp_impl = str(wl.get("engine", {}).get("warp_impl", "xla"))
    engine = RenderEngine(   # as serve_cli.py builds it
        max_bucket=serve_cfg.max_bucket,
        cache=MPICache(capacity_bytes=serve_cfg.cache_bytes,
                       quant=serve_cfg.cache_quant),
        encode_retries=serve_cfg.encode_retries,
        encode_backoff_ms=serve_cfg.encode_backoff_ms,
        use_alpha=bool(config.get("mpi.use_alpha", False)),
        is_bg_depth_inf=bool(config.get("mpi.is_bg_depth_inf", False)),
        backend=backend, warp_impl=warp_impl, warp_band=WARP_BAND)
    harness.say("backends: composite=%s warp=%s; bucket %d, %d requests, "
                "wait %.1f ms, cache %s" % (
                    backend, warp_impl, serve_cfg.max_bucket,
                    serve_cfg.max_requests, serve_cfg.max_wait_ms,
                    serve_cfg.cache_quant))

    n_images = int(wl["images"])
    images = make_images(n_images, H, W, seed)
    resident = bool(wl.get("resident_at_start", True))

    def encode(img, into):
        """serve_cli.py's encode path: one VideoGenerator per image, its
        planes deposited in `into`'s cache. Returns the cache key."""
        gen = VideoGenerator(config, params, batch_stats, img,
                             chunk=serve_cfg.max_bucket, engine=into,
                             encoder_quant=serve_cfg.encoder_quant)
        return gen.image_id

    def encode_on_miss(img):
        """The engine's `encode_fn` for a request that carries its pixels:
        the same encode, through a scratch float32 cache (exact)."""
        scratch = RenderEngine(max_bucket=1, cache=MPICache(quant="float32"))
        e = scratch.cache.get(encode(img, scratch))
        return e.planes[:, 0:3], e.planes[:, 3:4], e.disparity, e.K

    engine.encode_fn = encode_on_miss
    t0 = time.perf_counter()
    ids = []
    # a cache that starts cold still needs the encode program and the
    # render buckets warm: encode what the warm-up renders, drop it after
    warm_images = images if resident else images[:serve_cfg.max_requests]
    for k, img in enumerate(warm_images):
        ids.append(encode(img, engine))
        if k == 0:
            jax.block_until_ready(engine.cache.get(ids[0]).planes)
            harness.say("first encode (compile or cache load + run) in "
                        "%.1fs" % (time.perf_counter() - t0))
    jax.block_until_ready(engine.cache.get(ids[-1]).planes)
    harness.say("%d images encoded in %.1fs; cache %s" % (
        len(ids), time.perf_counter() - t0, engine.cache.stats()))
    if len(set(ids)) != len(ids):
        raise harness.BenchError("the seeded images are not distinct")

    trajectories, _ = generate_trajectories(config.get("data.name",
                                                       "_default"))
    poses = np.concatenate(trajectories).astype(np.float32)

    # warm every pair the batcher can emit: R distinct images in a batch of
    # P requests runs the render program of (pow2 R, pow2 P) after eager
    # stack / pad / slice ops whose shapes depend on R and P themselves
    t0 = time.perf_counter()
    max_req = min(serve_cfg.max_requests, len(ids))
    calls = 0
    for p in range(1, serve_cfg.max_requests + 1):
        for r in range(1, min(p, max_req) + 1):
            reqs = [(ids[j % r], poses[(j * 7) % len(poses)])
                    for j in range(p)]
            # (a bounded cache may have dropped a warm image again: then
            # the request carries its pixels, as the window's will)
            engine.render_many(reqs, images=None if resident else [
                warm_images[j % r] for j in range(p)])
            calls += 1
    harness.say("%d warm-up render calls in %.1fs" % (
        calls, time.perf_counter() - t0))

    batcher = ContinuousBatcher(   # as ServeFleet builds it
        engine, max_requests=serve_cfg.max_requests,
        max_wait_ms=serve_cfg.max_wait_ms, start=True, slo=None,
        auto_trace=False, admission=None,
        default_tier=serve_cfg.default_tier,
        request_deadline_ms=serve_cfg.request_deadline_ms)
    for fut in [batcher.submit(
            ids[j % len(ids)], poses[j],
            image=None if resident else warm_images[j % len(ids)])
            for j in range(2 * serve_cfg.max_requests)]:
        fut.result(timeout=DRAIN_TIMEOUT_S)
    temp_bytes = _bucket_temp_bytes(engine, ids, poses, serve_cfg)
    pixels = None
    if not resident:
        for image_id in ids:
            engine.cache.pop(image_id)
        ids = ["image%04d" % k for k in range(n_images)]
        pixels = images
    return {"cell": cell, "config": config, "engine": engine,
            "batcher": batcher, "ids": ids, "pixels": pixels,
            "poses": poses, "seed": seed, "spans": spans,
            "serve_cfg": serve_cfg, "devices": devices,
            "warp_band": WARP_BAND, "temp_bytes": temp_bytes}


def _bucket_temp_bytes(engine, ids, poses, serve_cfg) -> int:
    """Scratch of the largest render bucket, from the compiler's memory
    analysis (the allocator's peak does not count it on this backend)."""
    import jax.numpy as jnp

    from mine_tpu import geometry
    try:
        r = min(serve_cfg.max_requests, len(ids))
        p = serve_cfg.max_requests
        entries = [engine.cache.get(i) for i in ids[:r]]
        planes = jnp.stack([e.planes for e in entries])
        scales = (jnp.stack([e.scales for e in entries])
                  if entries[0].scales is not None else None)
        disp = jnp.stack([e.disparity for e in entries])
        K = jnp.stack([e.K for e in entries])
        args = (planes, scales, disp, K, geometry.inverse_intrinsics(K),
                jnp.zeros((p,), jnp.int32),
                jnp.asarray(poses[:p], jnp.float32))
        analysis = engine._render.lower(*args, engine.warp_impl).compile() \
            .memory_analysis()
        return int(getattr(analysis, "temp_size_in_bytes", 0) or 0)
    except Exception as e:  # noqa: BLE001 - a missing analysis is not a fault
        harness.say("no memory analysis of the render bucket: %r" % (e,))
        return 0


# ---------------- the window ----------------

def _registry():
    from mine_tpu import telemetry
    snap = telemetry.REGISTRY.snapshot("serve.")
    hist = telemetry.REGISTRY.get("serve.batcher.coalesce_size")
    if hist is not None:
        edges, counts = hist.bucket_counts()
        snap["serve.batcher.coalesce_size#buckets"] = [
            list(edges), list(counts)]
    return snap


def offer(ctx, sched, spans):
    """Submit the schedule in real time from this thread; returns per
    request (scheduled, submitted, done, ok) instants relative to the
    start, and the results kept for the reference check."""
    batcher, ids, poses = ctx["batcher"], ctx["ids"], ctx["poses"]
    pixels = ctx["pixels"]
    n = len(sched["t"])
    t_sub = [None] * n
    t_done = [None] * n
    errors = [None] * n
    futures = [None] * n
    t0 = time.perf_counter()

    def on_done(i):
        def cb(fut):
            t_done[i] = time.perf_counter() - t0
            errors[i] = fut.exception()
        return cb

    for i in range(n):
        with spans.span("serve.wait"):
            delay = sched["t"][i] - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
        with spans.span("serve.submit"):
            t_sub[i] = time.perf_counter() - t0
            k = sched["image"][i]
            fut = batcher.submit(ids[k], poses[sched["pose"][i]],
                                 image=None if pixels is None else pixels[k])
            fut.add_done_callback(on_done(i))
            futures[i] = fut
    return t0, t_sub, t_done, errors, futures


def measure(ctx, seconds, tracer, watch):
    wl, spans = ctx["cell"].workload, ctx["spans"]
    sched = schedule(wl, ctx["seed"], seconds, len(ctx["ids"]),
                     len(ctx["poses"]))
    n = len(sched["t"])
    calls0 = ctx["engine"].device_calls
    reg0 = _registry()
    spans.recording = True
    wall0 = time.time()
    if tracer is not None:
        tracer.start_after(0.3 * seconds)
    t0, t_sub, t_done, errors, futures = offer(ctx, sched, spans)
    with spans.span("window.sync"):
        rest = seconds - (time.perf_counter() - t0)
        if rest > 0:
            time.sleep(rest)
    window_s = time.perf_counter() - t0
    wall1 = time.time()
    reg1 = _registry()
    calls1 = ctx["engine"].device_calls
    spans.recording = False
    if tracer is not None:
        tracer.join()

    # ---- after the window: drain, read, check ----
    deadline = time.perf_counter() + DRAIN_TIMEOUT_S
    results = [None] * n
    for i, fut in enumerate(futures):
        try:
            results[i] = fut.result(timeout=max(
                0.0, deadline - time.perf_counter()))
        except Exception as e:  # noqa: BLE001 - a failed request is counted
            errors[i] = errors[i] or e
    finite = [r is not None and bool(np.isfinite(r[0]).all()
                                     and np.isfinite(r[1]).all())
              for r in results]
    failed = sum(1 for i in range(n) if errors[i] is not None
                 or not finite[i])
    lat_ms = [((t_done[i] - sched["t"][i]) * 1e3
               if errors[i] is None and finite[i] and t_done[i] is not None
               else float("inf")) for i in range(n)]
    late_ms = [(t_sub[i] - sched["t"][i]) * 1e3 for i in range(n)]
    done_in_window = sum(1 for i in range(n) if lat_ms[i] != float("inf")
                         and t_done[i] <= seconds)
    backlog_end = n - sum(1 for i in range(n) if t_done[i] is not None
                          and t_done[i] <= seconds)
    in_window = watch.between(wall0, wall1)

    ref = check_against_reference(ctx, sched, results)
    spread = [float(np.std(r[0])) for r in results if r is not None]
    checks = {
        "no_failed_request": failed == 0,
        "views_not_constant": bool(spread) and min(spread) > 0,
        "no_compile_in_window": not in_window,
        "no_encode_in_window": ctx["pixels"] is not None or (
            reg1.get("serve.sync_encode", 0) == reg0.get(
                "serve.sync_encode", 0)),
        "matches_reference": ref["ok"],
    }
    harness.say("requests %d, failed %d, completed in the window %d, still "
                "queued or in flight at its end %d; compile requests in "
                "window: %s" % (n, failed, done_in_window, backlog_end,
                                in_window))
    harness.say("latency ms p50 %.2f p95 %.2f max %.2f; generator late ms "
                "p95 %.3f max %.3f" % (
                    harness.percentile(lat_ms, 50),
                    harness.percentile(lat_ms, 95), max(lat_ms),
                    harness.percentile(late_ms, 95), max(late_ms)))
    harness.say("reference: %s" % ref)
    harness.say("checks: %s" % checks)
    traced_views = None
    if tracer is not None and tracer.span is not None:
        a, b = tracer.span[0] - t0, tracer.span[1] - t0
        traced_views = sum(1 for i in range(n) if t_done[i] is not None
                           and a <= t_done[i] <= b)
    cfg = ctx["config"]
    return {
        "window_start": wall0, "window_s": window_s,
        "attempted": n, "failed": failed,
        "correct": all(checks.values()), "checks": checks,
        "end_to_end": {
            "serve_views_per_s": done_in_window / seconds,
            "serve_latency_p50_ms": harness.percentile(lat_ms, 50),
            "serve_latency_p95_ms": harness.percentile(lat_ms, 95)},
        "counters": {"requests": n, "views_in_window": done_in_window,
                     "backlog_at_end": backlog_end,
                     "device_calls": calls1 - calls0,
                     "gen_late_p95_ms": harness.percentile(late_ms, 95),
                     "latency_p50_ms": harness.percentile(lat_ms, 50),
                     "latency_p95_ms": harness.percentile(lat_ms, 95),
                     "views_in_trace_window": traced_views,
                     "render_program": "_render_impl"},
        "registry": {"start": reg0, "end": reg1},
        "shapes": {"kind": "serve",
                   "planes": int(cfg.get("mpi.num_bins_coarse", 32))
                   + int(cfg.get("mpi.num_bins_fine", 0) or 0),
                   "height": int(cfg["data.img_h"]),
                   "width": int(cfg["data.img_w"]),
                   "band": ctx["warp_band"]},
        "temp_bytes": ctx["temp_bytes"],
        "lat_ms": lat_ms,
    }


def check_against_reference(ctx, sched, results):
    """A seeded sample of the served views against benchmark/reference.py
    on the same cached (dequantized) planes and pose."""
    import jax.numpy as jnp

    from benchmark import reference
    engine, ids, poses = ctx["engine"], ctx["ids"], ctx["poses"]
    k = int(ctx["cell"].workload.get("reference_views", 8))
    served = [i for i, r in enumerate(results) if r is not None
              and ids[sched["image"][i]] in engine.cache]
    if not served or k <= 0:
        return {"ok": False, "why": "no served view to compare"}
    rng = np.random.RandomState(harness.mix_seed(ctx["seed"], "reference"))
    sample = rng.choice(served, size=min(k, len(served)), replace=False)
    bg_inf = bool(ctx["config"].get("mpi.is_bg_depth_inf", False))
    errs, depth_errs, faults = [], [], {}
    for n_done, i in enumerate(sample):
        entry = engine.cache.get(ids[sched["image"][i]])
        planes = entry.dequantized()
        pose = jnp.asarray(poses[sched["pose"][i]])

        def ref_rgb(p):
            return np.asarray(reference.render_view(
                p, entry.disparity, entry.K, pose, is_bg_depth_inf=bg_inf)[0])

        rgb, depth = (np.asarray(x) for x in reference.render_view(
            planes, entry.disparity, entry.K, pose, is_bg_depth_inf=bg_inf))
        errs.append(float(np.mean(np.abs(results[i][0] - rgb))))
        depth_errs.append(float(np.mean(np.abs(results[i][1] - depth)
                                        / (np.abs(depth) + 1e-3))))
        if n_done == 0:
            # what faults do to the reference itself
            for name, faulty in (
                    ("order_reversed", planes[::-1]),
                    ("nearest_plane_dropped", planes.at[0, 3].set(0.0))):
                faults[name] = float(np.mean(np.abs(ref_rgb(faulty) - rgb)))
    ok = (max(errs) <= REFERENCE_MEAN_ABS_TOL and max(errs)
          <= REFERENCE_FRACTION_OF_FAULT * faults["order_reversed"])
    return {"ok": ok, "views": len(errs), "rgb_mean_abs_err_max": max(errs),
            "rgb_mean_abs_err_mean": sum(errs) / len(errs),
            "depth_mean_rel_err_max": max(depth_errs),
            "tolerance": REFERENCE_MEAN_ABS_TOL,
            "faults_change_the_reference_by": faults}


def teardown(ctx):
    if not ctx["batcher"].close(timeout=DRAIN_TIMEOUT_S):
        raise harness.BenchError("the batcher's thread did not stop")
    for t in threading.enumerate():
        if t.name == "mine-tpu-serve-batcher" and t.is_alive():
            raise harness.BenchError("a batcher thread is still alive")
