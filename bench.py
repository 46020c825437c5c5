#!/usr/bin/env python
"""Benchmark: LLFF-config training throughput on the real TPU chip.

Measures the full jitted train step (forward + 4-scale loss + backward +
two-group Adam) on the north-star config — LLFF 384x256, N=32 planes,
ResNet-50 backbone, bfloat16 conv stacks (BASELINE.md / BASELINE.json:
"LLFF 384x256 N=32 training at >=4x the V100x2 images/sec").

Sweeps a small variant grid — per-chip batch size and the Pallas kernel
backends (training.warp_backend / composite_backend = pallas_diff, the
banded warp + fused composite custom-VJP pairs) — and reports the FASTEST
as the headline number.

Every variant runs in its OWN SUBPROCESS under a watchdog, one after the
other; this parent never initialises JAX, so each child has the chip to
itself (a chip belongs to one process at a time). Isolation turns a hang or
an OOM into a recorded per-variant error instead of a driver hang:

  * child touches INIT_OK after jax.devices() succeeds — if that never
    appears no device could be initialised and the sweep aborts (remaining
    variants would each eat the full timeout for nothing);
  * a variant that compiles-then-hangs or OOMs is killed and recorded,
    and the next variant still gets a fresh client;
  * compiled executables persist across children via the JAX compilation
    cache (utils.configure_compile_cache: JAX_COMPILATION_CACHE_DIR where
    set, else .jax_cache/ in the checkout), so subprocess isolation
    doesn't pay recompiles.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": "images/sec", "vs_baseline": N,
   "best_config": "...", "variants": {name: images/sec | "error: ..."}}

vs_baseline uses the documented V100x2 reference estimate in BASELINE.md
(ESTIMATED_REFERENCE_IMAGES_PER_SEC below): the repo publishes no measured
number and this container has no GPU to measure one (SURVEY.md section 6),
so the denominator is an engineering estimate of the reference's 2xV100
fp32 throughput at its shipped config — recorded, not guessed silently.

Env knobs:
  MINE_TPU_BENCH_PROFILE=<dir>   capture a jax.profiler trace of the winner
  MINE_TPU_BENCH_VARIANTS=a,b    run only the named variants
  MINE_TPU_BENCH_SMOKE=1         tiny shapes / few steps — harness self-test
                                 on CPU, NOT a benchmark
  MINE_TPU_BENCH_INIT_TIMEOUT    seconds for child PJRT init (default 240)
  MINE_TPU_BENCH_VARIANT_TIMEOUT seconds per variant incl. compile
                                 (default 1800)

Physics audit: a reading whose implied FLOP rate exceeds the published
peak of the child's device_kind (analysis/costmodel.py CHIP_PEAKS) is
reported as "suspect", never as the headline; an unknown kind is an error.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

# Reference estimate: MINE on 2x V100 (B=2/GPU, fp32, 384x256, N=32).
# See BASELINE.md "Estimated reference throughput" for the derivation.
ESTIMATED_REFERENCE_IMAGES_PER_SEC = 4.0
# Documented spread of that estimate (BASELINE.md) — vs_baseline_range
# reports the multiplier at both edges instead of pretending the point
# denominator is exact.
REFERENCE_IMAGES_PER_SEC_SPREAD = (2.0, 6.0)
# FLOPs-grounded hard ceiling: 2x V100 fp32 peak (31.4 TFLOP/s) at 40%
# utilization over ~1.13 TFLOP/image (BASELINE.md "FLOPs-grounded
# bracket") — the reference cannot physically exceed this.
REFERENCE_FLOPS_CEILING_IMAGES_PER_SEC = 11.1

SMOKE = os.environ.get("MINE_TPU_BENCH_SMOKE") == "1"
HEIGHT, WIDTH = (64, 64) if SMOKE else (256, 384)
PLANES = 4 if SMOKE else 32
NUM_LAYERS = 18 if SMOKE else 50
WARMUP_STEPS = 1 if SMOKE else 3
# 60 steps ~ a few seconds at realistic speeds; 20 produced a 0.35 s sample
# whose 226 img/s reading implied >peak FLOP rate (see _measure's readback)
MEASURE_STEPS = 2 if SMOKE else 60

INIT_TIMEOUT = float(os.environ.get("MINE_TPU_BENCH_INIT_TIMEOUT",
                                    60 if SMOKE else 240))
VARIANT_TIMEOUT = float(os.environ.get("MINE_TPU_BENCH_VARIANT_TIMEOUT",
                                       300 if SMOKE else 1800))

# name -> (batch, config overrides)
#
# Ordering matters: the proven-fastest variant runs FIRST so an aborted
# sweep still leaves a headline number. B=8 variants are BANNED: at
# 256x384 N=32 the decoder's B*S=256 activation volume exceeds the v5e's
# 16 GB HBM (measured 2026-07-31 on an earlier tree: xla_b8 0.55 img/s,
# xla_b8_remat 0.30 img/s). B<=4 fits. RAW
# (unchunked) b8 variants stay banned; b8_chunk4 below re-enters B=8
# through plane-chunked decoding, which bounds the live activations to one
# chunk.
VARIANTS = {
    # shipped defaults (pallas warp+composite since the round-4 flip):
    # THE headline row. Measured 7.989 img/s on v5e (2026-08-01).
    "flagship_b4": (4, {}),
    # the reference-style XLA gather/scatter warp, pinned explicitly now
    # that defaults flipped: 0.595 img/s measured on v5e (the gather
    # fusions are ~95% of the step — round-4 notes in git history)
    "xla_b4": (4, {"training.warp_backend": "xla",
                   "training.composite_backend": "xla"}),
    "pallas_b4": (4, {"training.warp_backend": "pallas_diff",
                      "training.composite_backend": "pallas_diff"}),
    "pallas_bf16_b4": (4, {"training.warp_backend": "pallas_diff",
                           "training.composite_backend": "pallas_diff",
                           "training.warp_dtype": "bfloat16"}),
    # band32_b4/band24_b4 MEASURED round 5 and removed: warp_band
    # right-sizing is domain-limited — at bench poses the guard rejects
    # bands narrower than 48 and every step gather-falls-back (0.707 /
    # 0.605 img/s). 48 is the empirical floor; the guard + the
    # warp_fallback_frac metric made the experiment semantics-safe.
    # NOTE round 4: variants below inherit the shipped "auto" backends
    # (pallas on TPU). Names no longer carry an xla_ prefix — a prefixed
    # name measuring the Pallas path would corrupt cross-round comparisons
    # (pre-r4 JSON rows named xla_* measured the gather backend).
    "bf16warp_b4": (4, {"training.warp_dtype": "bfloat16"}),
    "remat_b4": (4, {"training.remat": "dots"}),
    "flagship_b2": (2, {}),
    "pallas_b2": (2, {"training.warp_backend": "pallas_diff",
                      "training.composite_backend": "pallas_diff"}),
    # the reference's EXACT shipped LLFF config (512x384, B=2/device —
    # configs/params_llff.yaml) for the apples-to-apples row; the headline
    # stays at the 384x256 north-star shape (BASELINE.json)
    "ref512_b2": (2, {"data.img_h": 384, "data.img_w": 512}),
    # coarse-to-fine on device (round-2 VERDICT item 10): the fine path
    # (uniform coarse + pdf-sampled fine planes, mpi_rendering.py:244-271)
    # was CPU-tested only. 32+32 planes at B=2 keeps B*S=128 = the b4 load.
    "c2f_b2": (2, {"mpi.num_bins_fine": 32}),
    # packed-head decoder (model.decoder_variant, models/decoder.py): the
    # stride-2->1 stage computes at stride 2 with 4x channels + a
    # depth-to-space head, lifting the reference architecture's worst MXU
    # lane-occupancy stage (16/128 lanes -> 64/128; lane table of the
    # round-3 notes, git history). Parity note: exact phase-decomposition init from reference
    # checkpoints exists (interior-exact); measured here to decide whether
    # the past-the-ceiling lever is worth recommending.
    "packed_b4": (4, {"model.decoder_variant": "packed"}),
    # B=8 re-entry via plane-chunked decoding (4 chunks of 8 planes, each
    # under remat -> backward holds one chunk's activations; models/mpi.py).
    # The raw b8 variants overflowed HBM and wedged the grant; this is the
    # designed fix. Kept LAST in sweep order: if it still thrashes, the
    # headline numbers are already on disk.
    "b8_chunk4": (8, {"training.decoder_plane_chunks": 4}),
    # LOSS-GRAPH-ONLY row (not a train-step variant): times value_and_grad
    # of compute_losses over frozen decoder outputs — the "73 ms elementwise
    # tail" region the PR-2 fused-pyramid pass restructures. Measurable
    # without a full soak; compare against the pre-fusion row in
    # BENCH_NOTES to price the shared-pyramid/batched-SSIM win on chip.
    "losspass_b4": (4, {}),
    # STAGED-PIPELINE row (not a fused-step variant): the GPipe-style
    # executor (mine_tpu/parallel/pipeline.py) driving the four staged
    # sub-programs — encoder / decoder / warp+composite / fused loss —
    # fwd+bwd with gradient accumulation, swept over stages x microbatches
    # (stages > 1 only when the visible device count divides; stage wall
    # timing off inside the timed region so the overlapped schedule is
    # what's measured). One parseable stderr curve line; JSON ips = the
    # 1-stage x 1-microbatch reading — the staged step at its closest to
    # the fused program, so the fused-vs-staged dispatch overhead is
    # directly readable against flagship_b4.
    "pipepass_b4": (4, {}),
    # WARP-ONLY row (not a train-step variant): times homography_warp
    # fwd+bwd in isolation on fixed decoder outputs — losspass_b4 one layer
    # deeper — once per warp backend (xla / pallas_diff; per-backend img/s
    # on stderr, JSON ips = the pallas_diff reading). The band-fit guard
    # applies and the in_domain stderr field says which path the
    # pallas_diff row actually timed.
    "warppass_b4": (4, {}),
    # RENDER-ONLY SERVING row (not a train-step variant): one synthetic MPI
    # encoded outside the timed region and cached (bf16), then
    # RenderEngine.render — fused dequant + warp + composite, forward only,
    # host round-trip included — timed once per warp backend (per-backend
    # views/s on stderr; JSON ips = the platform's default warp path). The
    # serve-side complement of warppass_b4: what one view request costs
    # once its encode is resident (mine_tpu/serve; README "Serving").
    "renderpass_b4": (4, {}),
    # ENCODE-AMORTIZATION curve (not a train-step variant): views/s of
    # (1 encode + v renders) for v = 1..64 — the economic case for the
    # encode-once serving engine as one monotone parseable stderr line;
    # JSON ips = the v=64 reading (its asymptote is renderpass throughput).
    "serve_amortize": (1, {}),
    # SERVING SLO curve (not a train-step variant): OPEN-LOOP Poisson
    # arrivals against the engine + micro-batcher — requests land at
    # scheduled exponential-gap times whether or not the server keeps up,
    # so queueing delay appears in the latency the instant offered load
    # exceeds capacity (closed-loop rows like renderpass can never show
    # that). One parseable stderr line of offered-QPS : p50 : p99 :
    # achieved-QPS points; JSON ips = the knee-of-curve throughput (the
    # highest offered rate the stack still served at >= 0.9x).
    "serve_slo": (1, {}),
    # COLD-REPLICA p99 A/B (not a train-step variant): first-request
    # latencies on a freshly constructed engine, AOT executable store ON
    # (boots by deserializing compiled artifacts — serve/aot.py) vs OFF
    # (pays live jit per pose bucket inline), plus the fully-warm p99 the
    # ROADMAP success metric compares against. JSON ips = the cold-p99
    # store-off / store-on ratio (> 1 means the store wins); the persistent
    # compile cache is disabled inside this variant's subprocess so the
    # off arm can't cheat by reading this process's own compiles back.
    "serve_coldstart": (1, {}),
    # STREAMING-SESSION curve (not a train-step variant): a synthetic
    # drifting video driven through a StreamSession per keyframe cadence
    # K in {1,2,4,8,16} — frames/s (encode amortized over K) and PSNR vs
    # the K=1 arm (per-frame encode, the exact reference) as one parseable
    # stderr line, plus a knee line (largest K holding >= 30 dB). Each arm
    # asserts the sync-encode invariant: exactly ceil(frames/K) encodes
    # per session. JSON ips = frames/s at the knee cadence.
    "stream_session": (1, {}),
    # MULTI-HOST ring sweep (not a train-step variant; CPU subprocess
    # hosts, no checkpoint): 2 -> 3 -> 4 hostnet processes boot from ONE
    # packed AOT artifact — every host must join with zero live compiles
    # — and a RingFront floods renders at each ring size. Aggregate
    # views/s + remote-route fraction + payload bytes/view per host
    # count as one parseable stderr line ("serve_multihost curve:
    # H:views_per_sec:remote_frac:bytes_per_view ..."), plus a failover
    # reading with one member drained so the
    # remote fraction is exercised, not just reported as zero. JSON ips
    # = views/s at the largest healthy ring; checkouts predating the
    # variant skip the row through the unknown-variant path, which the
    # bench conductor reads as neutral.
    "serve_multihost": (1, {}),
    # FLAKY-LINK arm of the multi-host row: the same ring flood through
    # policy-armed HostClients (serve.net.*: bounded retry, breaker,
    # keep-alive) with injected per-attempt latency and a deterministic
    # every-4th mid-request drop from testing/faults.py. The reading is
    # GOODPUT (ok views/s — failures excluded) plus the retry rate the
    # hardening paid to hold it; the row quantifies what the wire
    # hardening buys on a lossy link instead of asserting it. JSON ips =
    # goodput; checkouts predating serve.net.* skip the row through the
    # same unknown-variant path the conductor reads as neutral.
    "serve_multihost_flaky": (1, {}),
    # BINARY-WIRE arm of the multi-host row (serve.wire.*): the same
    # 2-host ring flood swept over codec json -> bin_f32 -> bin_int8,
    # binary arms riding mtpu-wire1 frames + the front's owner-coalescer.
    # Reading per arm: views/s, measured payload bytes/view (client
    # tx+rx deltas over the flood) and retry rate, as one parseable
    # stderr line ("serve_multihost_wire curve:
    # codec:views_per_sec:bytes_per_view:retry_rate ...") plus a pinned
    # serve.wire_point event per arm. The row asserts the tentpole's
    # claim: bin_int8 + coalescing moves >= 3x fewer bytes/view than
    # JSON/base64 with zero failed requests. JSON ips = bin_int8
    # views/s; checkouts predating serve.wire.* skip the row through the
    # same unknown-variant path the conductor reads as neutral.
    "serve_multihost_wire": (1, {}),
    # SSIM-PRECISION A/B row: two losspass measurements over the same
    # program, training.ssim_precision=highest (shipped default, exact-f32
    # blur einsums) vs default (platform precision — bf16 MXU on TPU).
    # The decision number for flipping the shipped default (ROADMAP "SSIM
    # blur precision" item); JSON ips = the "highest" reading, directly
    # comparable to losspass_b4.
    "ssim_precision_ab": (4, {}),
    # END-TO-END pipeline-fed loop (not a resident-batch device-step
    # variant): threaded batch assembly + double-buffered device staging
    # feeding the jitted step, fresh batch every step with the input
    # buffers donated. Measures what train_cli actually achieves — the
    # round-5 soak showed ~0.8 s/step real vs 0.22 s device-step, and this
    # row is the regression gauge for that gap. Donation is safe here
    # (and only here) because no batch is ever re-fed.
    "realloop_b4": (4, {"training.donate_batch": True}),
}


def _variant_config(name, extra=None):
    """Variant config; `extra` layers measurement-local overrides on top of
    the variant's own (the A/B rows run one program twice with one knob
    flipped — the knob is the measurement's, not the variant's)."""
    from mine_tpu.config import CONFIG_DIR, load_config
    batch, overrides = VARIANTS[name]
    config = load_config(os.path.join(CONFIG_DIR, "params_llff.yaml"))
    config.update({
        "data.img_h": HEIGHT, "data.img_w": WIDTH,
        "mpi.num_bins_coarse": PLANES,
        "model.num_layers": NUM_LAYERS,
        "training.dtype": "float32" if SMOKE else "bfloat16",
        "data.per_gpu_batch_size": batch,
    })
    config.update(overrides)
    config.update(extra or {})
    if SMOKE:  # harness self-test: tiny shapes beat any variant override
        config.update({"data.img_h": HEIGHT, "data.img_w": WIDTH})
    return config, batch


def build_variant_program(name, extra=None):
    """(trainer, state, batch) for a variant — THE program a measurement
    runs."""
    import jax.numpy as jnp

    from mine_tpu.data.synthetic import make_batch
    from mine_tpu.train.step import SynthesisTrainer

    config, batch_size = _variant_config(name, extra=extra)
    trainer = SynthesisTrainer(config, steps_per_epoch=10_000)
    state = trainer.init_state(batch_size=batch_size)
    h, w = int(config["data.img_h"]), int(config["data.img_w"])
    batch = {k: jnp.asarray(v) for k, v in
             make_batch(batch_size, h, w, num_points=256).items()}
    return trainer, state, batch


def _measure_realloop(name, steps=MEASURE_STEPS, keep_run=False):
    """Pipeline-fed end-to-end measurement (the realloop_* variants).

    Unlike _measure, nothing is resident: every step consumes a FRESH
    batch assembled by data/pipeline.threaded_pair_batches and staged by
    DeviceStager (the exact train-loop feed path), so host assembly, H2D,
    and the donated-buffer step all land in the measured wall-clock."""
    import itertools

    import jax

    from mine_tpu.data.pipeline import DeviceStager
    from mine_tpu.data.synthetic import SyntheticPairDataset
    from mine_tpu.train.step import SynthesisTrainer

    config, batch_size = _variant_config(name)
    trainer = SynthesisTrainer(config, steps_per_epoch=10_000)
    state = trainer.init_state(batch_size=batch_size)
    h, w = int(config["data.img_h"]), int(config["data.img_w"])
    # 2B+1 views -> 2B consecutive pairs: every epoch holds two full
    # batches of distinct items, so shuffled epochs exercise real
    # assembly work instead of replaying one cached batch
    ds = SyntheticPairDataset(num_views=2 * batch_size + 1,
                              num_points=256, height=h, width=w)
    workers = int(config.get("data.num_workers", 4) or 0)

    def host_batches():
        for epoch in itertools.count():
            yield from ds.batch_iterator(
                batch_size=batch_size, shuffle=True, seed=0, epoch=epoch,
                drop_last=True, workers=workers,
                prefetch_batches=int(config.get("data.prefetch_batches", 2)))

    staged = iter(DeviceStager(
        host_batches(), trainer.put_batch,
        depth=int(config.get("data.staging_buffers", 2))))

    first = next(staged)
    lowered = trainer._train_step.lower(state, first.batch)
    tflops = None
    try:
        tflops = lowered.cost_analysis().get("flops", 0.0) / 1e12 or None
    except Exception:
        pass
    step_fn = lowered.compile()

    state, metrics = step_fn(state, first.batch)  # donated: used once
    for _ in range(WARMUP_STEPS - 1):
        state, metrics = step_fn(state, next(staged).batch)
    jax.block_until_ready(metrics)

    def run(n):
        nonlocal state, metrics
        t0 = time.perf_counter()
        for _ in range(n):
            state, metrics = step_fn(state, next(staged).batch)
        # chained device->host readback, same audit rationale as _measure
        float(jax.device_get(jax.tree.leaves(metrics)[0]))
        return time.perf_counter() - t0

    dt = run(steps)
    print("  realloop: %d pipeline-fed steps in %.3fs (%.1f ms/step)"
          % (steps, dt, 1e3 * dt / steps), file=sys.stderr)
    return batch_size * steps / dt, tflops, (run if keep_run else None), \
        batch_size


def _measure_losspass(name, steps=MEASURE_STEPS, keep_run=False, extra=None):
    """Loss-graph-only measurement (the losspass_* variants).

    The model forward runs ONCE outside the timed region (exactly the key
    derivation _grads_and_metrics uses); the timed executable is
    value_and_grad of compute_losses with respect to the four mpi pyramids —
    the 4-scale render + photometric/SSIM/smoothness graph in isolation.
    This is the region the fused-pyramid pass restructures, so its ms/step
    is readable here without soaking a full train step. Steps don't chain
    through state, but the device queue serializes identical dispatches, so
    fetching the last step's loss still bounds all n executions."""
    import jax

    from mine_tpu.train import loss as loss_mod
    from mine_tpu.train.step import sample_disparity

    trainer, state, batch = build_variant_program(name, extra=extra)
    batch_size = int(batch["src_img"].shape[0])

    key = jax.random.fold_in(state.rng, state.step)
    d_key, f_key, drop_key = jax.random.split(key, 3)
    disparity = sample_disparity(d_key, batch_size, trainer.cfg)
    mpi_list, disparity_all, _ = trainer._forward(
        state.params, state.batch_stats, batch, disparity, f_key, drop_key,
        train=True)
    mpi_list = jax.block_until_ready(list(mpi_list))

    cfg, mesh = trainer.cfg, trainer.mesh

    def loss_only(mpis, disp, bt):
        total, metrics, _ = loss_mod.compute_losses(mpis, disp, bt, cfg,
                                                    mesh=mesh)
        return total, metrics

    lowered = jax.jit(jax.value_and_grad(loss_only, has_aux=True)).lower(
        mpi_list, disparity_all, batch)
    tflops = None
    try:
        tflops = lowered.cost_analysis().get("flops", 0.0) / 1e12 or None
    except Exception:
        pass
    loss_fn = lowered.compile()

    for _ in range(WARMUP_STEPS):
        (total, _), _grads = loss_fn(mpi_list, disparity_all, batch)
    jax.block_until_ready(total)

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            (total, _), _grads = loss_fn(mpi_list, disparity_all, batch)
        float(jax.device_get(total))
        return time.perf_counter() - t0

    dt = run(steps)
    print("  losspass: %d loss fwd+bwd in %.3fs (%.1f ms/step, loss graph "
          "only)" % (steps, dt, 1e3 * dt / steps), file=sys.stderr)
    return batch_size * steps / dt, tflops, (run if keep_run else None), \
        batch_size


def _measure_pipepass(name, steps=MEASURE_STEPS, keep_run=False):
    """Staged-pipeline measurement (the pipepass_* variants).

    Builds the variant trainer with training.pipeline.enabled and drives
    the executor's step (host-scheduled fill/drain over the four staged
    sub-programs) on a resident batch, once per (stages, microbatches)
    sweep point. Stage counts beyond 1 need a mesh: they're included only
    when the visible device count is divisible, with the variant's batch
    kept GLOBAL (not per-device) so every point runs the same problem.
    Executor stage timing is disabled inside the timed region — the
    block_until_ready telemetry would serialize the very overlap this row
    prices. Points where microbatches don't divide the batch are skipped.
    JSON ips = the 1-stage x 1-microbatch point."""
    import dataclasses

    import jax
    import numpy as np

    from mine_tpu.data.synthetic import make_batch
    from mine_tpu.parallel import mesh as mesh_lib
    from mine_tpu.train.step import SynthesisTrainer

    ndev = len(jax.devices())
    stage_counts = [1] + [s for s in (2, 4)
                          if ndev > 1 and ndev % s == 0 and s <= ndev]
    batch_size, _ = VARIANTS[name]
    micro_counts = [m for m in (1, 2, 4) if batch_size % m == 0]

    points = []  # (stages, microbatches, ips, run_fn)
    for stages in stage_counts:
        config, _ = _variant_config(name, extra={
            "training.pipeline.enabled": True,
            "training.pipeline.stages": stages,
            "training.pipeline.microbatches": 1,
        })
        mesh = mesh_lib.make_mesh() if stages > 1 else None
        trainer = SynthesisTrainer(config, mesh=mesh, steps_per_epoch=10_000)
        state = trainer.init_state(batch_size=batch_size)
        h, w = int(config["data.img_h"]), int(config["data.img_w"])
        batch = trainer.put_batch(
            {k: np.asarray(v) for k, v in
             make_batch(batch_size, h, w, num_points=256).items()})
        for micro in micro_counts:
            trainer._pipeline.cfg = dataclasses.replace(
                trainer._pipeline.cfg, microbatches=micro)
            trainer._pipeline.time_stages = False

            for _ in range(WARMUP_STEPS):
                state, metrics = trainer.train_step(state, batch)
            jax.block_until_ready(metrics)

            def run(n, trainer=trainer, batch=batch):
                nonlocal state
                t0 = time.perf_counter()
                for _ in range(n):
                    state, metrics = trainer.train_step(state, batch)
                # chained through state: the last loss bounds all n steps
                float(jax.device_get(jax.tree.leaves(metrics)[0]))
                return time.perf_counter() - t0

            n = max(1, steps // 2)  # sweep row: half-length per point
            dt = run(n)
            ips = batch_size * n / dt
            points.append((stages, micro, ips,
                           run if (stages, micro) == (1, 1) else None))
            print("  pipepass: stages=%d microbatches=%d -> %.1f ms/step "
                  "(%.3f img/s)" % (stages, micro, 1e3 * dt / n, ips),
                  file=sys.stderr)

    # one parseable curve line (the bench-notes contract, like
    # "amortize curve:"): s<stages>xm<microbatches>=img/s pairs
    print("  pipepass curve: " + " ".join(
        "s%dxm%d=%.3f" % (s, m, ips) for s, m, ips, _ in points),
        file=sys.stderr)
    head = next((p for p in points if p[0] == 1 and p[1] == 1), points[0])
    return head[2], None, (head[3] if keep_run else None), batch_size


# the warppass / renderpass sub-sweep: the gather reference, then the
# banded Pallas pair (the warppass JSON headline)
WARPPASS_BACKENDS = ("xla", "pallas_diff")


def _measure_warppass(name, steps=MEASURE_STEPS, keep_run=False):
    """Warp-only measurement (the warppass_* variants).

    losspass_b4 one layer deeper: the model forward runs ONCE outside the
    timed region, the scale-0 warp inputs are derived exactly as
    loss_per_scale derives them (unit scale factor), and each warp backend
    gets its own jitted value_and_grad of sum(homography_warp(volume))
    with respect to the 4-channel plane volume. Per-backend img/s and the
    in-domain flag go to stderr (a 0.0 flag means that row priced the
    gather FALLBACK, not the banded path — same honesty rule as the
    warp_fallback_frac training metric); the JSON ips is the pallas_diff
    backend's reading."""
    import math

    import jax
    import jax.numpy as jnp

    from mine_tpu import geometry
    from mine_tpu.ops import warp
    from mine_tpu.train import loss as loss_mod
    from mine_tpu.train.step import sample_disparity

    trainer, state, batch = build_variant_program(name)
    batch_size = int(batch["src_img"].shape[0])
    cfg = trainer.cfg

    key = jax.random.fold_in(state.rng, state.step)
    d_key, f_key, drop_key = jax.random.split(key, 3)
    disparity = sample_disparity(d_key, batch_size, trainer.cfg)
    mpi_list, disparity_all, _ = trainer._forward(
        state.params, state.batch_stats, batch, disparity, f_key, drop_key,
        train=True)

    # scale-0 warp inputs, derived as loss_per_scale derives them
    # (train/loss.py) with a unit scale factor
    p0 = loss_mod.build_scale_plan(batch, cfg, num_scales=1)[0]
    mpi = mpi_list[0]                                    # [B,S,4,H,W]
    B, S, _, H, W = mpi.shape
    G_tgt_src = jax.lax.stop_gradient(
        geometry.rigid_inverse(batch["G_src_tgt"]))
    volume = mpi[:, :, 0:4].reshape(B * S, 4, H, W)
    depths = (1.0 / disparity_all).reshape(B * S)

    def expand(x):
        return jnp.repeat(x, S, axis=0)

    G_e, Ki_e, Kt_e = (expand(G_tgt_src), expand(p0.K_src_inv),
                       expand(p0.K_tgt))
    grid = geometry.cached_pixel_grid(H, W)
    volume = jax.block_until_ready(volume)

    head_ips, head_tflops, head_run = None, None, None
    for impl in WARPPASS_BACKENDS:

        def warp_sum(vol, _impl=impl):
            out, _, flag = warp.homography_warp(
                vol, depths, G_e, Ki_e, Kt_e, grid, impl=_impl,
                band=cfg.warp_band, with_domain_flag=True)
            return jnp.sum(out), flag

        lowered = jax.jit(
            jax.value_and_grad(warp_sum, has_aux=True)).lower(volume)
        tflops = None
        try:
            tflops = lowered.cost_analysis().get("flops", 0.0) / 1e12 or None
        except Exception:
            pass
        fn = lowered.compile()
        for _ in range(WARMUP_STEPS):
            (total, flag), _g = fn(volume)
        jax.block_until_ready(total)

        def run(n, _fn=fn):
            t0 = time.perf_counter()
            for _ in range(n):
                (total, _flag), _g = _fn(volume)
            float(jax.device_get(total))
            return time.perf_counter() - t0

        dt = run(steps)
        ips = batch_size * steps / dt
        in_domain = float(jax.device_get(flag))
        print("  warppass[%s]: %d warp fwd+bwd in %.3fs (%.2f ms/step, "
              "%.3f img/s, in_domain=%s)"
              % (impl, steps, dt, 1e3 * dt / steps, ips,
                 "n/a" if math.isnan(in_domain) else "%.2f" % in_domain),
              file=sys.stderr)
        if impl == "pallas_diff":
            head_ips, head_tflops, head_run = ips, tflops, run
    return head_ips, head_tflops, (head_run if keep_run else None), batch_size


def _serve_bench_engine(trainer, state, batch, max_bucket=8, mesh_batch=1):
    """(engine, image_id, encode_fn) for the serving-engine rows: one
    synthetic MPI cached under the default bf16 quant, the engine wired the
    way serve_cli wires it (composite backend by platform). mesh_batch > 1
    builds a MeshRenderEngine spanning that many devices on the "batch"
    axis instead (the --mesh fleet rows)."""
    import jax

    from mine_tpu.kernels import on_tpu_backend
    from mine_tpu.serve import MeshRenderEngine, MPICache, RenderEngine
    from mine_tpu.train.step import sample_disparity

    cfg = trainer.cfg
    batch_size = int(batch["src_img"].shape[0])
    key = jax.random.fold_in(state.rng, state.step)
    d_key, f_key, drop_key = jax.random.split(key, 3)
    disparity = sample_disparity(d_key, batch_size, cfg)

    def encode(img, disp):
        return trainer.model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            img, disp, train=False)[0]

    encode_jit = jax.jit(encode)
    mpi = jax.block_until_ready(encode_jit(batch["src_img"], disparity))

    engine_kw = dict(
        use_alpha=cfg.use_alpha,
        is_bg_depth_inf=cfg.is_bg_depth_inf,
        backend="pallas" if on_tpu_backend() else "xla",
        warp_band=cfg.warp_band,
        max_bucket=max_bucket,
        cache=MPICache(quant="bf16"))
    engine = (MeshRenderEngine(mesh_batch=mesh_batch, **engine_kw)
              if mesh_batch > 1 else RenderEngine(**engine_kw))
    image_id = "bench"
    engine.put(image_id, mpi[0, :, 0:3], mpi[0, :, 3:4], disparity[0],
               batch["K_src"][0])
    return engine, image_id, encode_jit, (batch["src_img"], disparity), mpi


def _serve_bench_poses(n):
    """[n,4,4] small-translation poses — inside every banded backend's
    correctness domain, like the video trajectories' near poses."""
    import numpy as np
    poses = np.tile(np.eye(4, dtype=np.float32), (n, 1, 1))
    poses[:, 2, 3] = -0.02 * (np.arange(n) % 8)
    return poses


def _render_cost_tflops(engine, image_id, poses):
    """HLO cost analysis of ONE bucketed render call (advisory)."""
    import jax
    import jax.numpy as jnp

    from mine_tpu import geometry

    entry = engine.cache.get(image_id)
    planes, disp = entry.planes[None], entry.disparity[None]
    K = entry.K[None]
    scales = entry.scales[None] if entry.scales is not None else None
    K_inv = geometry.inverse_intrinsics(K)
    idx = jnp.zeros(poses.shape[0], jnp.int32)
    try:
        lowered = jax.jit(
            engine._render_impl, static_argnames=("warp_impl",)).lower(
            planes, scales, disp, K, K_inv, idx, jnp.asarray(poses),
            warp_impl=engine.warp_impl)
        return lowered.cost_analysis().get("flops", 0.0) / 1e12 or None
    except Exception:
        return None


def _measure_renderpass(name, steps=MEASURE_STEPS, keep_run=False):
    """Render-only serving forward (the renderpass_* variants).

    The OTHER half of the encode/render split the serving engine monetizes:
    one synthetic MPI is encoded outside the timed region and cached (bf16),
    then each warp backend times `RenderEngine.render` — dequant + per-plane
    homography warp + composite, forward only, through the engine's bucketed
    jitted program, host round-trip included (what a serve request pays).
    Per-backend views/s on stderr; the JSON ips is the engine's DEFAULT
    warp path on this platform (pallas_diff on TPU, xla elsewhere)."""
    from mine_tpu.kernels import on_tpu_backend

    trainer, state, batch = build_variant_program(name)
    batch_size = int(batch["src_img"].shape[0])
    engine, image_id, _, _, _ = _serve_bench_engine(
        trainer, state, batch, max_bucket=max(4, batch_size))
    poses = _serve_bench_poses(batch_size)
    default_impl = "pallas_diff" if on_tpu_backend() else "xla"

    head_ips, head_tflops, head_run = None, None, None
    for impl in WARPPASS_BACKENDS:
        engine.render(image_id, poses, warp_impl=impl)  # compile + warm

        def run(n, _impl=impl):
            t0 = time.perf_counter()
            for _ in range(n):
                engine.render(image_id, poses, warp_impl=_impl)
            # engine.render returns numpy: every call already round-trips
            return time.perf_counter() - t0

        dt = run(steps)
        ips = batch_size * steps / dt
        print("  renderpass[%s]: %d render-only calls of %d poses in %.3fs "
              "(%.2f ms/call, %.3f views/s)%s"
              % (impl, steps, batch_size, dt, 1e3 * dt / steps, ips,
                 " [default]" if impl == default_impl else ""),
              file=sys.stderr)
        if impl == default_impl:
            engine.warp_impl = impl
            head_ips, head_run = ips, run
            head_tflops = _render_cost_tflops(engine, image_id, poses)
    return head_ips, head_tflops, (head_run if keep_run else None), batch_size


# views-per-encode sweep of the amortization row (pow2 so every render
# decomposes into already-compiled buckets)
SERVE_AMORTIZE_VIEWS = (1, 2, 4, 8, 16, 32, 64)


def _bench_mesh_sizes():
    """Fleet sizes for the serve-row mesh sweep: the MINE_TPU_BENCH_MESH
    env var (set from the --mesh CLI flag; bench children inherit it),
    validated pow2. Empty when --mesh wasn't given — the serve rows then
    keep their exact legacy single-device output."""
    raw = os.environ.get("MINE_TPU_BENCH_MESH", "")
    sizes = []
    for tok in raw.split(","):
        tok = tok.strip()
        if not tok:
            continue
        n = int(tok)
        if n < 1 or (n & (n - 1)):
            raise ValueError(
                "--mesh fleet sizes must be powers of two >= 1, got %r" % tok)
        sizes.append(n)
    return sizes


def _measure_serve_amortize(name, steps=MEASURE_STEPS, keep_run=False):
    """Encode-amortization curve (the serve_amortize variant).

    For each v in the sweep, time ONE full encode (model forward + cache
    put) plus v engine renders, and report v / t as views/s. The curve is
    v/(t_enc + v*t_render) — monotonically increasing by construction, and
    its asymptote is the render-only throughput: the number the encode-once
    architecture is buying. Printed as one parseable stderr line
    ("serve_amortize curve: v:views_per_sec ..."); JSON ips is the v=64
    reading, tflops_per_step the full v=64 trial (1 encode + 64 renders)
    with batch=64 so the physics audit prices the whole trial.

    With --mesh (MINE_TPU_BENCH_MESH), one EXTRA parseable line per fleet
    size — "serve_amortize[mesh=N] curve: v:views_per_sec_per_chip ..." —
    times the same trial through a MeshRenderEngine spanning N devices on
    the "batch" axis and divides by N: the per-chip efficiency a fleet
    operator compares against the single-device row. Fleet sizes exceeding
    the visible device count are skipped with a loud stderr note."""
    import jax

    trainer, state, batch = build_variant_program(name)
    max_bucket = 8
    engine, image_id, encode_jit, enc_args, mpi = _serve_bench_engine(
        trainer, state, batch, max_bucket=max_bucket)
    img, disparity = enc_args
    repeats = 1 if SMOKE else 3

    engine.warmup(image_id)  # pre-compile every pose bucket <= max_bucket

    def one_trial(v, eng=engine):
        t0 = time.perf_counter()
        out = jax.block_until_ready(encode_jit(img, disparity))
        eng.put(image_id, out[0, :, 0:3], out[0, :, 3:4], disparity[0],
                batch["K_src"][0])
        eng.render(image_id, _serve_bench_poses(v))
        return time.perf_counter() - t0

    curve = []
    for v in SERVE_AMORTIZE_VIEWS:
        t = min(one_trial(v) for _ in range(repeats))
        curve.append((v, v / t))
    print("  serve_amortize curve: "
          + " ".join("%d:%.3f" % (v, ips) for v, ips in curve)
          + "  (views/s per single-image encode)", file=sys.stderr)

    for n_chips in _bench_mesh_sizes():
        avail = len(jax.devices())
        if n_chips > avail:
            print("  serve_amortize[mesh=%d]: skipped — only %d device(s) "
                  "visible" % (n_chips, avail), file=sys.stderr)
            continue
        m_engine = engine if n_chips == 1 else _serve_bench_engine(
            trainer, state, batch, max_bucket=max_bucket,
            mesh_batch=n_chips)[0]
        m_engine.warmup(image_id)
        m_curve = []
        for v in SERVE_AMORTIZE_VIEWS:
            t = min(one_trial(v, m_engine) for _ in range(repeats))
            m_curve.append((v, v / t / n_chips))
        print("  serve_amortize[mesh=%d] curve: " % n_chips
              + " ".join("%d:%.3f" % (v, ips) for v, ips in m_curve)
              + "  (views/s PER CHIP, %d-device fleet)" % n_chips,
              file=sys.stderr)

    v_max = SERVE_AMORTIZE_VIEWS[-1]
    tflops = None
    try:
        enc_tflops = encode_jit.lower(
            img, disparity).cost_analysis().get("flops", 0.0) / 1e12
        render_tflops = _render_cost_tflops(
            engine, image_id, _serve_bench_poses(max_bucket)) or 0.0
        tflops = enc_tflops + render_tflops * (v_max // max_bucket) or None
    except Exception:
        pass

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            one_trial(v_max)
        return time.perf_counter() - t0

    return curve[-1][1], tflops, (run if keep_run else None), v_max


# offered-rate sweep of the SLO row, as fractions of the measured
# closed-loop base throughput: below / at / past the capacity knee
SERVE_SLO_RATE_FRACS = (0.25, 0.5, 0.75, 1.0, 1.25)
# the deliberate overload point: offered rate past calibrated capacity,
# replayed with admission control ON and mixed tiers — proves the shed /
# degrade ladder engages under real queue pressure (serve/admission.py)
SERVE_SLO_OVERLOAD_FRAC = 1.5


def _measure_serve_slo(name, steps=MEASURE_STEPS, keep_run=False):
    """Open-loop Poisson SLO bench (the serve_slo variant).

    Calibrates the stack's closed-loop base throughput, then replays a
    fixed-seed Poisson arrival schedule through the micro-batcher at
    offered rates spanning the knee. Per-request latency is completion
    minus SCHEDULED arrival (not submit time): under overload the
    generator never slows down, so queueing delay accumulates into p99
    exactly as a real client would see it. Reported per rate: p50/p99
    latency and achieved QPS (n / last-completion); the knee is the
    highest offered rate still achieving >= 0.9x offered. Each point also
    lands in the telemetry event stream ("serve.slo_point"). After the
    curve, ONE deliberate overload point (SERVE_SLO_OVERLOAD_FRAC x
    capacity) replays with admission control enabled and a tier-0 request
    mixed in every 4th slot, printing served/shed/degraded/expired — the
    curve itself stays admission-free so runs remain comparable.

    With --mesh (MINE_TPU_BENCH_MESH), the full calibrate+sweep repeats
    per fleet size through a MeshRenderEngine, printing
    "serve_slo[mesh=N] curve/knee" lines (mesh=N also lands in the
    slo_point events); fleet sizes exceeding the device count are skipped
    loudly. The JSON ips stays the legacy single-device knee.

    Trace-sampled mode: MINE_TPU_BENCH_TRACE_SAMPLE=<rate in (0,1]> turns
    on request tracing (telemetry/tracing.py) for the sweep — every
    sampled request emits its trace.span tree into the event stream, and
    each rate point prints a per-span mean breakdown (queue/pad/render) so
    a latency knee decomposes into WHERE the time went, not just how much."""
    import jax
    import numpy as np

    from mine_tpu.serve.batcher import MicroBatcher
    from mine_tpu.telemetry import tracing

    trace_sample = float(
        os.environ.get("MINE_TPU_BENCH_TRACE_SAMPLE", "0") or 0)
    if trace_sample > 0:
        tracing.configure(sample=trace_sample, recent_capacity=4096)

    trainer, state, batch = build_variant_program(name)
    max_bucket = 8
    engine, image_id, _, _, _ = _serve_bench_engine(
        trainer, state, batch, max_bucket=max_bucket)
    poses = _serve_bench_poses(max_bucket)
    n_req = 24 if SMOKE else 64

    def sweep(eng, tag, chips):
        """Calibrate + Poisson-sweep one engine; returns (knee, base_qps)."""
        eng.warmup(image_id)  # compiles never pollute a latency percentile

        # closed-loop calibration: full-bucket renders -> views/s capacity
        calls = 2 if SMOKE else 10
        t0 = time.perf_counter()
        for _ in range(calls):
            eng.render(image_id, poses)
        base_qps = calls * max_bucket / (time.perf_counter() - t0)

        rng = np.random.RandomState(0)  # fixed schedule: runs comparable
        curve = []  # (offered, p50_ms, p99_ms, achieved)
        for frac in SERVE_SLO_RATE_FRACS:
            offered = base_qps * frac
            sched = np.cumsum(rng.exponential(1.0 / offered, size=n_req))
            batcher = MicroBatcher(eng, max_requests=max_bucket,
                                   max_wait_ms=2.0)
            done_at = [None] * n_req

            def _cb(i):
                def record(_fut, _i=i):
                    done_at[_i] = time.perf_counter()
                return record

            futs = []
            t_start = time.perf_counter()
            for i in range(n_req):
                # open loop: sleep until the SCHEDULED arrival — never
                # longer because the server is behind (the whole point)
                lag = sched[i] - (time.perf_counter() - t_start)
                if lag > 0:
                    time.sleep(lag)
                fut = batcher.submit(image_id, poses[i % max_bucket])
                fut.add_done_callback(_cb(i))
                futs.append(fut)
            for fut in futs:
                fut.result()
            batcher.close()
            lat_ms = np.asarray(
                [(done_at[i] - t_start - sched[i]) * 1e3
                 for i in range(n_req)])
            achieved = n_req / (max(done_at) - t_start)
            p50, p99 = np.percentile(lat_ms, [50, 99])
            curve.append((offered, float(p50), float(p99), achieved))
            from mine_tpu import telemetry
            telemetry.emit("serve.slo_point", offered_qps=round(offered, 3),
                           p50_ms=round(float(p50), 3),
                           p99_ms=round(float(p99), 3),
                           achieved_qps=round(achieved, 3), n_requests=n_req,
                           mesh=chips)
            if trace_sample > 0:
                # the batcher head-sampled its own traces (MicroBatcher
                # auto_trace); this point's are the freshest n_req
                traces = [t for t in tracing.recent(n_req)
                          if t["name"] == "serve.request"]
                by_span = {}
                for t in traces:
                    for s in t["spans"]:
                        if s["parent"] is not None:
                            by_span.setdefault(s["name"], []).append(s["ms"])
                breakdown = " ".join(
                    "%s=%.1f" % (k, sum(v) / len(v))
                    for k, v in sorted(by_span.items()))
                print("  %s traces@%.2fqps: n=%d %s (mean ms/span)"
                      % (tag, offered, len(traces), breakdown),
                      file=sys.stderr)

        print("  %s curve: " % tag
              + " ".join("%.2f:%.1f:%.1f:%.2f" % pt for pt in curve)
              + "  (offered_qps:p50_ms:p99_ms:achieved_qps)",
              file=sys.stderr)
        # highest offered rate the stack still kept up with; when even the
        # lightest point missed (tiny smoke schedules drown in batcher
        # linger), fall back to the best achieved rate — the capacity
        # estimate
        knee = max((pt[0] for pt in curve if pt[3] >= 0.9 * pt[0]),
                   default=max(pt[3] for pt in curve))
        print("  %s knee: %.2f qps (base closed-loop %.2f views/s)"
              % (tag, knee, base_qps), file=sys.stderr)

        # one deliberate overload point: offered > calibrated capacity,
        # admission ON, every 4th request best-effort (tier 0) — the
        # controller should shed/degrade the low tier while the standard
        # tier keeps completing (the curve above stays admission-free)
        from mine_tpu import telemetry
        from mine_tpu.serve.admission import (AdmissionController,
                                              RequestShed)
        offered = base_qps * SERVE_SLO_OVERLOAD_FRAC
        sched = np.cumsum(rng.exponential(1.0 / offered, size=n_req))
        admission = AdmissionController(
            enabled=True, burn_max=0.0, queue_high=max_bucket,
            inflight_high=0, shed_factor=2.0)
        batcher = MicroBatcher(eng, max_requests=max_bucket,
                               max_wait_ms=2.0, admission=admission)
        done_at = [None] * n_req
        futs = []
        t_start = time.perf_counter()
        for i in range(n_req):
            lag = sched[i] - (time.perf_counter() - t_start)
            if lag > 0:
                time.sleep(lag)
            fut = batcher.submit(image_id, poses[i % max_bucket],
                                 tier=0 if i % 4 == 0 else 1)
            fut.add_done_callback(_cb(i))
            futs.append(fut)
        served = shed = 0
        lat_ms = []
        for i, fut in enumerate(futs):
            try:
                fut.result()
                served += 1
                lat_ms.append((done_at[i] - t_start - sched[i]) * 1e3)
            except RequestShed:
                shed += 1
        batcher.close()
        p99 = float(np.percentile(lat_ms, 99)) if lat_ms else float("nan")
        print("  %s overload@%.2fqps: served=%d shed=%d degraded=%d "
              "expired=%d p99=%.1fms (admission on, tier0 every 4th)"
              % (tag, offered, served, shed, admission.degraded,
                 batcher.expired, p99), file=sys.stderr)
        telemetry.emit("serve.slo_point", offered_qps=round(offered, 3),
                       p50_ms=round(float(np.percentile(lat_ms, 50)), 3)
                       if lat_ms else None,
                       p99_ms=round(p99, 3) if lat_ms else None,
                       achieved_qps=round(
                           served / max(max(d for d in done_at
                                            if d is not None) - t_start,
                                        1e-9), 3) if served else 0.0,
                       n_requests=n_req, mesh=chips, overload=True,
                       shed=shed, degraded=admission.degraded,
                       expired=batcher.expired)
        return knee, base_qps

    knee, base_qps = sweep(engine, "serve_slo", 1)

    for n_chips in _bench_mesh_sizes():
        avail = len(jax.devices())
        if n_chips > avail:
            print("  serve_slo[mesh=%d]: skipped — only %d device(s) "
                  "visible" % (n_chips, avail), file=sys.stderr)
            continue
        m_engine = engine if n_chips == 1 else _serve_bench_engine(
            trainer, state, batch, max_bucket=max_bucket,
            mesh_batch=n_chips)[0]
        sweep(m_engine, "serve_slo[mesh=%d]" % n_chips, n_chips)

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            engine.render(image_id, poses)
        return time.perf_counter() - t0

    return knee, None, (run if keep_run else None), 1


def _measure_serve_coldstart(name, steps=MEASURE_STEPS, keep_run=False):
    """Cold-replica p99, AOT store on vs off (the serve_coldstart variant).

    Builds the artifact store once (one engine pays the compiles and
    writes back), then measures per-request latency of the FIRST n
    requests on a fresh engine two ways: store ON (warmup deserializes
    executables, zero live compiles) and store OFF (every pose bucket's
    first request pays jit inline). Requests cycle pose counts 1..bucket
    so every bucket's cold cost lands inside the measured window, matching
    the ROADMAP metric "p99 of the first 100 requests on a cold replica
    ~= warm p99". One parseable stderr line; JSON ips = the
    cold-p99-off / cold-p99-on ratio (> 1: the store wins)."""
    import tempfile

    import numpy as np
    import jax

    from mine_tpu.kernels import on_tpu_backend
    from mine_tpu.serve import AOTStore, MPICache, RenderEngine

    # the off arm must pay REAL compiles: the persistent compile cache
    # (configure_compile_cache in the parent) would hand it this very
    # process's builder compiles from disk. Per-variant subprocess
    # isolation makes this config flip safe.
    jax.config.update("jax_enable_compilation_cache", False)

    trainer, state, batch = build_variant_program(name)
    max_bucket = 8
    builder, image_id, _, _, _ = _serve_bench_engine(
        trainer, state, batch, max_bucket=max_bucket)
    entry = builder.cache.get(image_id)
    cfg = trainer.cfg
    store_dir = tempfile.mkdtemp(prefix="mtpu_aot_bench_")

    def fresh(store):
        engine = RenderEngine(
            use_alpha=cfg.use_alpha,
            is_bg_depth_inf=cfg.is_bg_depth_inf,
            backend="pallas" if on_tpu_backend() else "xla",
            warp_band=cfg.warp_band,
            max_bucket=max_bucket,
            cache=MPICache(quant="bf16"),
            aot_store=store)
        engine.cache.adopt(image_id, entry)
        return engine

    # build once: this engine pays every bucket's compile and writes back
    fresh(AOTStore(store_dir)).warmup(image_id)

    n_req = 16 if SMOKE else 100
    poses = _serve_bench_poses(max_bucket)

    def first_requests(engine, warm_from_store):
        t_boot = time.perf_counter()
        if warm_from_store:
            engine.warmup(image_id)
        boot_ms = (time.perf_counter() - t_boot) * 1e3
        lat = []
        for i in range(n_req):
            k = (i % max_bucket) + 1  # cycle every pose bucket cold
            t0 = time.perf_counter()
            engine.render(image_id, poses[:k])
            lat.append((time.perf_counter() - t0) * 1e3)
        return boot_ms, np.asarray(lat)

    eng_on = fresh(AOTStore(store_dir))
    boot_on, lat_on = first_requests(eng_on, warm_from_store=True)
    eng_off = fresh(None)
    _, lat_off = first_requests(eng_off, warm_from_store=False)
    # the on-engine is now fully warm: its second window is the baseline
    # the ROADMAP metric compares the cold windows against
    _, lat_warm = first_requests(eng_on, warm_from_store=False)

    p99_on = float(np.percentile(lat_on, 99))
    p99_off = float(np.percentile(lat_off, 99))
    p99_warm = float(np.percentile(lat_warm, 99))
    print("  serve_coldstart: cold_p99_on=%.1fms cold_p99_off=%.1fms "
          "warm_p99=%.1fms boot_on=%.0fms loads=%d compiles_on=%d "
          "compiles_off=%d (p99 of first %d requests per arm)"
          % (p99_on, p99_off, p99_warm, boot_on, eng_on.bucket_loads,
             eng_on.bucket_compiles, eng_off.bucket_compiles, n_req),
          file=sys.stderr)
    speedup = p99_off / max(p99_on, 1e-9)
    print("  serve_coldstart: cold-replica p99 %.2fx better with store "
          "(cold/warm ratio on=%.2f off=%.2f)"
          % (speedup, p99_on / max(p99_warm, 1e-9),
             p99_off / max(p99_warm, 1e-9)), file=sys.stderr)
    from mine_tpu import telemetry
    telemetry.emit("serve.coldstart_point",
                   cold_p99_on_ms=round(p99_on, 3),
                   cold_p99_off_ms=round(p99_off, 3),
                   warm_p99_ms=round(p99_warm, 3),
                   boot_on_ms=round(boot_on, 3),
                   loads=eng_on.bucket_loads,
                   compiles_off=eng_off.bucket_compiles,
                   n_requests=n_req)

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            eng_on.render(image_id, poses)
        return time.perf_counter() - t0

    return speedup, None, (run if keep_run else None), 1


# keyframe cadences of the streaming-session sweep
STREAM_SESSION_CADENCES = (1, 2, 4, 8, 16)
# knee threshold: largest K whose PSNR vs the per-frame-encode arm holds
STREAM_SESSION_PSNR_DB = 30.0


def _measure_stream_session(name, steps=MEASURE_STEPS, keep_run=False):
    """Streaming-session cadence sweep (the stream_session variant).

    A synthetic drifting video (the bench batch's source image under a
    growing brightness gain + a slow dolly) streams through a fresh
    engine + ContinuousBatcher + StreamSession once per cadence
    K in STREAM_SESSION_CADENCES. Per arm: frames/s (wall-clock over the
    whole session, so the ceil(F/K) keyframe encodes are amortized in) and
    PSNR against the K=1 arm — per-frame encode, bitwise the reference
    path, so its own PSNR is inf and every K>1 reading is pure temporal-
    reuse drift. One parseable stderr line ("stream_session curve:
    K:fps:psnr_db ...") plus a knee line (largest K holding
    >= STREAM_SESSION_PSNR_DB). Each arm asserts the session invariant:
    sync_encodes grows by EXACTLY ceil(F/K) per session. JSON ips = the
    knee arm's frames/s; batch = frames per session."""
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mine_tpu.kernels import on_tpu_backend
    from mine_tpu.serve import (ContinuousBatcher, MPICache, RenderEngine,
                                SessionManager)
    from mine_tpu.train.step import sample_disparity

    trainer, state, batch = build_variant_program(name)
    cfg = trainer.cfg
    max_bucket = 8
    # >= the largest cadence, so every K arm does DIFFERENT encode work
    # (ceil(F/K) strictly decreasing) and the fps curve is monotone
    n_frames = 16 if SMOKE else 48
    repeats = 1 if SMOKE else 3

    key = jax.random.fold_in(state.rng, state.step)
    disparity = sample_disparity(jax.random.split(key, 1)[0], 1, cfg)
    K_src = np.asarray(batch["K_src"][0])

    def encode(img_1hw3, disp):
        return trainer.model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            img_1hw3, disp, train=False)[0]

    encode_jit = jax.jit(encode)

    def encode_frame(img_hwc):
        mpi = encode_jit(jnp.asarray(img_hwc, jnp.float32)[None], disparity)
        return mpi[0, :, 0:3], mpi[0, :, 3:4], disparity[0], K_src

    # synthetic drifting stream: brightness ramp + slow dolly — drift vs
    # the keyframe grows with age by construction, so the PSNR curve is
    # monotone in K
    base = np.asarray(batch["src_img"][0], np.float32)
    frames = [np.clip(base * (1.0 + 0.02 * i), 0.0, 1.0)
              for i in range(n_frames)]
    poses = np.tile(np.eye(4, dtype=np.float32), (n_frames, 1, 1))
    poses[:, 2, 3] = -0.004 * np.arange(n_frames)

    def one_arm(kf_every):
        engine = RenderEngine(
            use_alpha=cfg.use_alpha,
            is_bg_depth_inf=cfg.is_bg_depth_inf,
            backend="pallas" if on_tpu_backend() else "xla",
            warp_band=cfg.warp_band,
            max_bucket=max_bucket,
            cache=MPICache(quant="float32"),
            encode_fn=encode_frame)
        # absorb every pose-bucket compile before the timed session
        engine.put("warm", *encode_frame(frames[0]))
        engine.warmup("warm")
        engine.cache.pop("warm")
        batcher = ContinuousBatcher(engine, max_requests=max_bucket)
        manager = SessionManager(batcher, keyframe_every=kf_every)
        expect = -(-n_frames // kf_every)  # ceil
        try:
            best, rgb = None, None
            for _ in range(repeats):
                before = engine.sync_encodes
                session = manager.open()
                t0 = time.perf_counter()
                futs = [session.process_frame(frames[i], poses[i])
                        for i in range(n_frames)]
                out = [f.result() for f in futs]
                dt = time.perf_counter() - t0
                stats = session.stats()
                session.close()
                got = engine.sync_encodes - before
                assert got == expect, (
                    "stream_session[K=%d]: %d sync encodes per session, "
                    "expected ceil(%d/%d)=%d"
                    % (kf_every, got, n_frames, kf_every, expect))
                assert stats["failed_frames"] == 0
                if best is None or dt < best:
                    best = dt
                    rgb = np.stack([r[0] for r in out])
        finally:
            manager.close()
            batcher.close()
        return n_frames / best, rgb

    curve = []
    rgb_ref = None
    for kf_every in STREAM_SESSION_CADENCES:
        fps, rgb = one_arm(kf_every)
        if kf_every == 1:
            rgb_ref = rgb
            psnr = float("inf")  # the reference arm IS per-frame encode
        else:
            mse = float(np.mean((rgb - rgb_ref) ** 2))
            psnr = 10.0 * math.log10(1.0 / max(mse, 1e-12))
        curve.append((kf_every, fps, psnr))

    print("  stream_session curve: "
          + " ".join("%d:%.3f:%s" % (k, fps,
                                     "ref" if math.isinf(p) else "%.2f" % p)
                     for k, fps, p in curve)
          + "  (K:frames_per_sec:psnr_db_vs_K1, %d frames/session)"
          % n_frames, file=sys.stderr)
    knee = max((k for k, _, p in curve
                if p >= STREAM_SESSION_PSNR_DB or math.isinf(p)),
               default=1)
    knee_fps = next(fps for k, fps, _ in curve if k == knee)
    print("  stream_session knee: K=%d (%.3f frames/s, largest cadence "
          "holding >= %.0f dB vs per-frame encode)"
          % (knee, knee_fps, STREAM_SESSION_PSNR_DB), file=sys.stderr)

    from mine_tpu import telemetry
    telemetry.emit("serve.stream_point",
                   knee_cadence=knee,
                   knee_fps=round(knee_fps, 3),
                   n_frames=n_frames,
                   curve=" ".join("%d:%.3f" % (k, fps)
                                  for k, fps, _ in curve))

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            one_arm(knee)
        return time.perf_counter() - t0

    return knee_fps, None, (run if keep_run else None), n_frames


# host counts the serve_multihost variant sweeps (subprocess CPU hosts)
SERVE_MULTIHOST_COUNTS = (2, 3, 4)


def _measure_serve_multihost(name, steps=MEASURE_STEPS, keep_run=False):
    """Multi-host ring throughput sweep (the serve_multihost variant).

    Boots max(SERVE_MULTIHOST_COUNTS) hostnet subprocess hosts from ONE
    packed AOT artifact (the tools/aot_warmstore.py --pack unit: a builder
    subprocess pays every compile, each host must then join with
    aot_compiles == 0 — asserted), and floods a fixed request set through
    a RingFront per ring size H over the first H hosts. Requests carry
    their source image, so a key landing off its cached host sync-encodes
    in place — the same discipline as the chaos soak's failover traffic.
    After the healthy sweep, one extra reading repeats the largest ring
    with a member drained ring-side, so the remote-route fraction is a
    measured failover number instead of a structural zero. One parseable
    stderr line; JSON ips = views/s at the largest healthy ring.

    The serve_multihost_flaky variant reuses the same boot path with a
    2-host ring and policy-armed clients, floods through injected
    latency + drops, and reports GOODPUT and retry rate instead of the
    curve; serve_multihost_wire boots the hosts with `--wire binary`
    and sweeps the flood over codec json -> bin_f32 -> bin_int8 (binary
    arms with the owner-coalescer armed), reporting views/s +
    bytes/view + retry rate per codec and asserting the >= 3x bin_int8
    byte cut (see VARIANTS)."""
    import subprocess
    import tempfile

    import numpy as np

    from mine_tpu.serve import HostClient, HostRing, RingFront
    from mine_tpu.utils import refuse_on_tpu

    refuse_on_tpu("bench.py %s" % name)
    repo = os.path.dirname(os.path.abspath(__file__))
    counts = SERVE_MULTIHOST_COUNTS[:2] if SMOKE else SERVE_MULTIHOST_COUNTS
    if name.endswith("_flaky") or name.endswith("_wire"):
        counts = SERVE_MULTIHOST_COUNTS[:1]  # the LINK/WIRE is under test
    n_req = 24 if SMOKE else 128
    n_keys = 8
    workdir = tempfile.mkdtemp(prefix="mtpu_multihost_bench_")
    artifact = os.path.join(workdir, "aot.pack.tar")
    env = dict(os.environ, PYTHONPATH=repo)
    hostnet = [sys.executable, "-m", "mine_tpu.serve.hostnet"]
    warm_key, warm_seed = "00000001benchwarm", 11

    build = subprocess.run(
        hostnet + ["--host-id", "builder", "--build-artifact", artifact,
                   "--cache-shards", "1", "--warm-key", warm_key,
                   "--warm-seed", str(warm_seed)],
        env=env, cwd=repo, capture_output=True, text=True, timeout=600)
    assert build.returncode == 0, (
        "serve_multihost: artifact build failed: %s"
        % build.stderr[-300:])

    procs, handles = {}, {}

    def _cleanup():
        for hid, p in procs.items():
            if p.poll() is None:
                try:
                    handles[hid].drain()
                except Exception:  # noqa: BLE001 - hard-kill fallback
                    p.terminate()
        for p in procs.values():
            if p.poll() is None:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()

    try:
        for i in range(max(counts)):
            hid = "h%d" % i
            p = subprocess.Popen(
                hostnet + ["--host-id", hid, "--port", "0",
                           "--aot-artifact", artifact,
                           "--warm-key", warm_key,
                           "--warm-seed", str(warm_seed),
                           "--drain-timeout-s", "5"]
                + (["--wire", "binary"]
                   if name.endswith("_wire") else []),
                env=env, cwd=repo, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, text=True, bufsize=1)
            procs[hid] = p
            fields = {}
            while True:
                line = p.stdout.readline()
                if not line:
                    break
                fields = dict(kv.split("=", 1) for kv in line.split()
                              if "=" in kv)
                if fields.get("ready") == "1":
                    break
            assert fields.get("ready") == "1", (
                "serve_multihost: host %s failed to boot" % hid)
            assert int(fields.get("aot_compiles", -1)) == 0 and \
                int(fields.get("aot_loads", 0)) > 0, (
                "serve_multihost: host %s compiled live "
                "(loads=%s compiles=%s)"
                % (hid, fields.get("aot_loads"),
                   fields.get("aot_compiles")))
            handles[hid] = HostClient("127.0.0.1:%s" % fields["port"],
                                      timeout_s=300.0)

        pose = np.eye(4, dtype=np.float32)
        keys = ["%08x" % ((s * 2 ** 32) // n_keys + 1) + "bench%d" % s
                for s in range(n_keys)]
        # 32x32 uploads so the wire arms measure payload movement, not
        # frame-header overhead (synthetic_encode_fn only folds img.sum()
        # into its seed, so upload geometry is free to differ from SYN_HW)
        imgs = {k: np.full((32, 32, 3), 40.0 + i, np.float32)
                for i, k in enumerate(keys)}

        def flood(front, n):
            import concurrent.futures as cf
            t0 = time.perf_counter()
            futs = [front.submit(keys[i % n_keys], pose,
                                 image=imgs[keys[i % n_keys]])
                    for i in range(n)]
            cf.wait(futs, timeout=600)
            dt = time.perf_counter() - t0
            errs = [f for f in futs if f.exception() is not None]
            assert not errs, (
                "serve_multihost: %d flood requests failed: %r"
                % (len(errs), errs[0].exception()))
            return n / dt

        if name.endswith("_flaky"):
            # flaky-link arm: the same flood through policy-armed clients
            # while testing/faults.py injects 1 ms per-attempt latency and
            # a deterministic every-4th mid-request drop. Goodput counts
            # ONLY ok renders — a failure lowers the number instead of
            # aborting the row — and the retry counters price the
            # hardening that held it.
            import concurrent.futures as cf

            from mine_tpu.serve import NetPolicy
            from mine_tpu.testing import faults
            policy = NetPolicy(enabled=True, retries=3, backoff_ms=2.0,
                               breaker_threshold=1000)
            net = {hid: HostClient(handles[hid].address, timeout_s=300.0,
                                   policy=policy, net_src="bench",
                                   net_name=hid)
                   for hid in list(handles)[:counts[-1]]}
            ring = HostRing()
            front = RingFront(ring, {}, policy=policy)
            for hid, c in net.items():
                front.add_host(hid, c)
            try:
                flood(front, max(n_req // 4, n_keys))  # clean warm-up
                faults.set_plan(faults.FaultPlan(net_latency_ms=1,
                                                 net_drop_every=4))
                t0 = time.perf_counter()
                futs = [front.submit(keys[i % n_keys], pose,
                                     image=imgs[keys[i % n_keys]])
                        for i in range(n_req)]
                cf.wait(futs, timeout=600)
                dt = time.perf_counter() - t0
            finally:
                faults.set_plan(None)
                front.close()
            ok = sum(f.exception() is None for f in futs)
            retries = sum(c.retries for c in net.values())
            reconnects = sum(c.reconnects for c in net.values())
            goodput = ok / dt
            print("  serve_multihost_flaky: hosts=%d goodput=%.3f "
                  "retry_rate=%.3f retries=%d reconnects=%d failed=%d "
                  "(ok views/s under net_latency_ms=1 net_drop_every=4, "
                  "%d req)"
                  % (counts[-1], goodput, retries / n_req, retries,
                     reconnects, n_req - ok, n_req), file=sys.stderr)
            from mine_tpu import telemetry
            telemetry.emit("serve.multihost_point", hosts=counts[-1],
                           views_per_sec=round(goodput, 3),
                           remote_frac=round(
                               front.remote_route_fraction(), 4))
            return goodput, None, None, 1

        if name.endswith("_wire"):
            # binary-wire arm: codec sweep over the same flood, with
            # fresh clients per arm so the bytes/view ledger is a clean
            # per-codec delta. Binary arms add the front's
            # owner-coalescer (linger window + full-bucket flush); the
            # json arm uses plain clients against the SAME advertising
            # hosts, so only the client's policy differs.
            from mine_tpu import telemetry
            from mine_tpu.serve import WirePolicy
            H = counts[-1]
            arms = []
            for codec in ("json", "bin_f32", "bin_int8"):
                wp = None
                if codec != "json":
                    wp = WirePolicy(format="binary", codec=codec[4:],
                                    coalesce_ms=5.0, coalesce_max=8)
                clients = {hid: HostClient(handles[hid].address,
                                           timeout_s=300.0,
                                           wire_policy=wp)
                           for hid in list(handles)[:H]}
                ring = HostRing()
                front = RingFront(ring, {}, wire=wp)
                for hid, c in clients.items():
                    front.add_host(hid, c)
                try:
                    # warm-up also settles negotiation, so the measured
                    # window is frames-only
                    flood(front, max(n_req // 4, n_keys))
                    b0 = sum(c.bytes_tx + c.bytes_rx
                             for c in clients.values())
                    vps = flood(front, n_req)
                    moved = sum(c.bytes_tx + c.bytes_rx
                                for c in clients.values()) - b0
                finally:
                    front.close()
                bpv = moved / n_req
                retries = sum(c.retries for c in clients.values())
                arms.append((codec, vps, bpv, retries / n_req))
                telemetry.emit("serve.wire_point", codec=codec,
                               views_per_sec=round(vps, 3),
                               bytes_per_view=round(bpv, 1))
            print("  serve_multihost_wire curve: "
                  + " ".join("%s:%.3f:%.0f:%.3f" % a for a in arms)
                  + "  (codec:views_per_sec:bytes_per_view:retry_rate, "
                  "%d req/arm, %d hosts)" % (n_req, H), file=sys.stderr)
            json_bpv, int8_bpv = arms[0][2], arms[2][2]
            assert int8_bpv * 3.0 <= json_bpv, (
                "serve_multihost_wire: bin_int8+coalescing moved %.0f "
                "bytes/view vs JSON's %.0f — less than the 3x cut the "
                "wire fabric promises" % (int8_bpv, json_bpv))
            return arms[2][1], None, None, 1

        def _bytes_moved(hids):
            return sum(handles[h].bytes_tx + handles[h].bytes_rx
                       for h in hids)

        def arm(H, drain_one=False):
            ring = HostRing()
            front = RingFront(ring, {})
            hids = list(handles)[:H]
            for hid in hids:
                front.add_host(hid, handles[hid])
            if drain_one:
                # ring-side mark only: the process stays up for later
                # arms; its range re-resolves ring-wise = pure failover
                ring.drain("h0", emit=False)
            try:
                flood(front, max(n_req // 4, n_keys))  # routing warm-up
                b0 = _bytes_moved(hids)
                vps = flood(front, n_req)
                bpv = (_bytes_moved(hids) - b0) / n_req
                return vps, front.remote_route_fraction(), bpv
            finally:
                front.close()

        curve = [(H,) + arm(H) for H in counts]
        fo_vps, fo_frac, fo_bpv = arm(counts[-1], drain_one=True)

        print("  serve_multihost curve: "
              + " ".join("%d:%.3f:%.3f:%.0f" % (H, vps, frac, bpv)
                         for H, vps, frac, bpv in curve)
              + " failover%d:%.3f:%.3f:%.0f" % (counts[-1], fo_vps,
                                                fo_frac, fo_bpv)
              + "  (hosts:views_per_sec:remote_frac:bytes_per_view, "
              "%d req/arm)" % n_req,
              file=sys.stderr)
        from mine_tpu import telemetry
        for H, vps, frac, _bpv in curve:
            telemetry.emit("serve.multihost_point", hosts=H,
                           views_per_sec=round(vps, 3),
                           remote_frac=round(frac, 4))

        def run(n):
            ring = HostRing()
            front = RingFront(ring, {})
            for hid in handles:
                front.add_host(hid, handles[hid])
            try:
                return flood(front, n)
            finally:
                front.close()

        if keep_run:
            import atexit
            atexit.register(_cleanup)  # hosts must outlive the closure
        return curve[-1][1], None, (run if keep_run else None), 1
    finally:
        if not keep_run:
            _cleanup()


def _measure_ssim_ab(name, steps=MEASURE_STEPS, keep_run=False):
    """training.ssim_precision A/B (the ssim_precision_ab variants).

    Two _measure_losspass runs of the SAME program with only the SSIM
    blur-einsum precision flipped: "highest" (shipped default, exact-f32)
    vs "default" (platform choice — bf16 MXU passes on TPU). The stderr
    speedup line is the decision number for flipping the shipped default;
    the returned ips is the "highest" reading so the row stays directly
    comparable with losspass_b4."""
    readings = {}
    for mode in ("highest", "default"):
        ips, tflops, run, batch = _measure_losspass(
            name, steps=steps, keep_run=(keep_run and mode == "highest"),
            extra={"training.ssim_precision": mode})
        readings[mode] = (ips, tflops, run)
        print("  ssim_precision_ab[%s]: %.3f img/s (loss graph only)"
              % (mode, ips), file=sys.stderr)
    print("  ssim_precision_ab: default/highest speedup %.2fx"
          % (readings["default"][0] / readings["highest"][0]),
          file=sys.stderr)
    ips, tflops, run = readings["highest"]
    return ips, tflops, run, batch


def _measure(name, steps=MEASURE_STEPS, keep_run=False):
    """Compile + run one variant.

    Returns (images_per_sec, tflops_per_step|None, run_fn|None);
    tflops_per_step is the HLO cost-analysis figure the parent uses to
    reject physically-impossible readings (> chip peak)."""
    import jax

    if name.startswith("realloop"):
        return _measure_realloop(name, steps=steps, keep_run=keep_run)
    if name.startswith("warppass"):
        return _measure_warppass(name, steps=steps, keep_run=keep_run)
    if name.startswith("renderpass"):
        return _measure_renderpass(name, steps=steps, keep_run=keep_run)
    if name.startswith("serve_amortize"):
        return _measure_serve_amortize(name, steps=steps, keep_run=keep_run)
    if name.startswith("serve_slo"):
        return _measure_serve_slo(name, steps=steps, keep_run=keep_run)
    if name.startswith("serve_coldstart"):
        return _measure_serve_coldstart(name, steps=steps,
                                        keep_run=keep_run)
    if name.startswith("stream_session"):
        return _measure_stream_session(name, steps=steps, keep_run=keep_run)
    if name.startswith("serve_multihost"):
        return _measure_serve_multihost(name, steps=steps,
                                        keep_run=keep_run)
    if name.startswith("ssim_precision"):
        return _measure_ssim_ab(name, steps=steps, keep_run=keep_run)
    if name.startswith("pipepass"):
        return _measure_pipepass(name, steps=steps, keep_run=keep_run)
    if name.startswith("losspass"):
        return _measure_losspass(name, steps=steps, keep_run=keep_run)

    trainer, state, batch = build_variant_program(name)
    batch_size = int(batch["src_img"].shape[0])

    # AOT: trace once, read the cost analysis off the lowering, compile the
    # same lowering (avoids the second trace a fresh jit call would pay —
    # tracing this step costs minutes on the 1-core host)
    lowered = trainer._train_step.lower(state, batch)
    tflops = None
    try:
        tflops = lowered.cost_analysis().get("flops", 0.0) / 1e12 or None
    except Exception:
        pass  # cost analysis is advisory; never fail the measurement
    step_fn = lowered.compile()

    for _ in range(WARMUP_STEPS):
        state, metrics = step_fn(state, batch)
    jax.block_until_ready(metrics)

    def run(n):
        nonlocal state, metrics
        t0 = time.perf_counter()
        for _ in range(n):
            state, metrics = step_fn(state, batch)
        # A real device->host readback of a computed value, not just
        # block_until_ready: the steps chain through `state`, so fetching
        # the LAST step's loss can only complete after every step's
        # compute. A 20-step sample once read 226 img/s, an implied
        # >peak 256 TFLOP/s (4.53 TFLOP/step per
        # jax.jit(...).lower(...).cost_analysis() vs the v5e's 197
        # TFLOP/s bf16), so the ready signal alone is not trusted.
        float(jax.device_get(jax.tree.leaves(metrics)[0]))
        return time.perf_counter() - t0

    dt = run(steps)
    print("  measured %d steps in %.3fs (%.1f ms/step)"
          % (steps, dt, 1e3 * dt / steps), file=sys.stderr)
    return batch_size * steps / dt, tflops, (run if keep_run else None), \
        batch_size


# ---------------------------------------------------------------- child

def write_result(outdir, payload):
    """Atomic result.json write — the watchdog protocol's child half.
    Shared by bench.py and tools/microbench.py."""
    with open(os.path.join(outdir, "result.json.tmp"), "w") as f:
        json.dump(payload, f)
    os.replace(os.path.join(outdir, "result.json.tmp"),
               os.path.join(outdir, "result.json"))


def _child(name: str, outdir: str) -> None:
    """Run one variant; touch INIT_OK after device init, write result.json."""
    def write(payload):
        write_result(outdir, payload)

    try:
        mesh_sizes = _bench_mesh_sizes()
        if SMOKE and mesh_sizes and max(mesh_sizes) > 1:
            # CPU smoke: the host platform exposes ONE device unless asked
            # for more — give the child enough virtual devices for the
            # largest requested fleet (must land before backend init)
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=%d"
                % max(mesh_sizes)).strip()
        if SMOKE:
            # smoke is a CPU harness self-test; never touch the chip
            os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        from mine_tpu.utils import configure_compile_cache
        configure_compile_cache()
        device_kind = jax.devices()[0].device_kind
        open(os.path.join(outdir, "INIT_OK"), "w").close()

        profile_dir = os.environ.get("MINE_TPU_BENCH_PROFILE")
        # the profile re-run only needs `run`; don't pay a full measurement
        ips, tflops, run, batch = _measure(
            name, steps=1 if profile_dir else MEASURE_STEPS,
            keep_run=bool(profile_dir))
        if profile_dir:
            jax.profiler.start_trace(profile_dir)
            run(5)
            jax.profiler.stop_trace()
            print("profiler trace (%s) in %s" % (name, profile_dir),
                  file=sys.stderr)
        write({"ips": ips, "tflops_per_step": tflops, "batch": batch,
               "device_kind": device_kind})
    except Exception as e:  # compile failure / OOM: record for the parent
        msg = (str(e).splitlines() or [repr(e)])[0][:200]
        write({"error": msg})


# ---------------------------------------------------------------- parent

def run_child_watchdog(cmd, outdir, init_timeout, body_timeout, env=None):
    """Supervise a child that touches INIT_OK then writes result.json.

    Returns (payload|None, error|None, wedged). `wedged` is True only for a
    genuine init-deadline expiry with the child still alive — a child that
    DIES without writing a result (segfault, OOM-kill) is a per-run error.
    """
    proc = subprocess.Popen(cmd, stdout=subprocess.DEVNULL, env=env)
    init_path = os.path.join(outdir, "INIT_OK")
    result_path = os.path.join(outdir, "result.json")

    def wait_for(path, deadline):
        """'found' | 'died' | 'timeout' (re-checks path after child exit)."""
        while True:
            if os.path.exists(path):
                return "found"
            if proc.poll() is not None:
                # give the filesystem a beat, then re-check once
                time.sleep(0.2)
                return "found" if os.path.exists(path) else "died"
            if time.time() >= deadline:
                return "timeout"
            time.sleep(0.5)

    def read_result():
        with open(result_path) as f:
            return json.load(f)

    status = wait_for(init_path, time.time() + init_timeout)
    if status != "found":
        proc.kill()
        proc.wait()
        if os.path.exists(result_path):  # child recorded its own error
            return None, read_result().get("error", "child died"), False
        if status == "died":
            return None, ("child died before device init "
                          "(rc=%s)" % proc.returncode), False
        return (None, "init timeout after %ds (no device came up)"
                % init_timeout, True)

    status = wait_for(result_path, time.time() + body_timeout)
    if status != "found":
        proc.kill()
        proc.wait()
        if os.path.exists(result_path):  # landed in the last poll window
            payload = read_result()
            if "error" in payload:
                return None, payload["error"], False
            return payload, None, False
        if status == "died":
            return None, "child died mid-run (rc=%s)" % proc.returncode, False
        # not flagged as a wedge: the NEXT child's init either succeeds (the
        # hang was variant-specific) or trips the init timeout (truly wedged)
        return (None, "timeout after %ds (compile/run hang)" % body_timeout,
                False)
    proc.wait()
    payload = read_result()
    if "error" in payload:
        return None, payload["error"], False
    return payload, None, False


def _run_variant(name: str, env_extra=None):
    """Spawn the child for `name`; returns (ips|None, error|None, wedged)."""
    outdir = tempfile.mkdtemp(prefix="bench_%s_" % name)
    env = dict(os.environ)
    env.pop("MINE_TPU_BENCH_PROFILE", None)
    env.update(env_extra or {})
    try:
        payload, err, wedged = run_child_watchdog(
            [sys.executable, os.path.abspath(__file__), "--child", name,
             outdir],
            outdir, INIT_TIMEOUT, VARIANT_TIMEOUT, env=env)
    finally:
        import shutil
        shutil.rmtree(outdir, ignore_errors=True)
    if payload is None:
        return None, err, wedged
    err = None if SMOKE else audit_reading(
        payload["ips"], payload.get("tflops_per_step"), payload.get("batch"),
        payload.get("device_kind"))
    if err is not None:
        return None, err, False
    return payload["ips"], None, False


def audit_reading(ips, tflops_per_step, batch, device_kind):
    """Physics audit of one variant reading; error string or None.

    A reading whose implied FLOP rate exceeds the published peak of the
    device it was taken on is a measurement artifact (observed once: 226
    img/s => 256 TFLOP/s on a 197 TFLOP/s part), not a result — refuse to
    report it as one. A device kind with no published peak is an error
    (costmodel.chip_model raises), never priced against another chip's."""
    if not tflops_per_step or not batch:
        return None  # cost analysis unavailable: nothing to audit against
    from mine_tpu.analysis.costmodel import chip_model
    peak = chip_model(device_kind)["peak_tflops"]
    implied = ips / batch * tflops_per_step
    if implied > peak:
        return ("suspect: %.1f img/s implies %.0f TFLOP/s > %.0f peak of "
                "%s (%.2f TFLOP/step)"
                % (ips, implied, peak, device_kind, tflops_per_step))
    return None


def main():
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        _child(sys.argv[2], sys.argv[3])
        return

    # --mesh [N,N,...] — fleet sizes for the serve rows (default 1,2,4).
    # Parsed by hand like --child (no argparse in this file); exported as
    # MINE_TPU_BENCH_MESH so the variant children inherit it.
    argv = sys.argv[1:]
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "--mesh":
            if i + 1 < len(argv) and not argv[i + 1].startswith("-"):
                os.environ["MINE_TPU_BENCH_MESH"] = argv[i + 1]
                i += 2
            else:
                os.environ["MINE_TPU_BENCH_MESH"] = "1,2,4"
                i += 1
        elif a.startswith("--mesh="):
            os.environ["MINE_TPU_BENCH_MESH"] = a.split("=", 1)[1]
            i += 1
        else:
            print("unknown argument %r (only --child and --mesh exist)" % a,
                  file=sys.stderr)
            sys.exit(2)
    if os.environ.get("MINE_TPU_BENCH_MESH"):
        _bench_mesh_sizes()  # fail fast on malformed sizes, in the parent

    only = os.environ.get("MINE_TPU_BENCH_VARIANTS")
    # default run = the flagship headline only: every variant pays its
    # own cold compile, so "all variants" is asked for by name
    names = [n.strip() for n in only.split(",") if n.strip()] if only \
        else ["flagship_b4"]
    # tolerate unknown names (variant lists live in shell scripts that
    # outlive sweep reshuffles — a stale name must not kill the whole
    # sweep): warn, record, run the rest
    unknown = [n for n in names if n not in VARIANTS]
    if unknown:
        print("WARNING: skipping unknown MINE_TPU_BENCH_VARIANTS %s "
              "(known: %s)" % (unknown, sorted(VARIANTS)), file=sys.stderr)
        names = [n for n in names if n in VARIANTS]
    if not names:
        print("no known variants left to run", file=sys.stderr)
        sys.exit(2)

    results = {n: "skipped: unknown variant" for n in unknown}
    best_name, best_ips = None, 0.0
    for i, name in enumerate(names):
        ips, err, wedged = _run_variant(name)
        if wedged:
            results[name] = "error: " + err
            for rest in names[i + 1:]:
                results[rest] = "skipped: no device came up"
            print("variant %s: %s — aborting sweep" % (name, err),
                  file=sys.stderr)
            break
        if err is not None:
            results[name] = "error: " + err
            print("variant %s failed: %s" % (name, err), file=sys.stderr)
            continue
        results[name] = round(ips, 3)
        print("variant %s: %.3f images/sec" % (name, ips), file=sys.stderr)
        if ips > best_ips:
            best_name, best_ips = name, ips

    metric = "LLFF 384x256 N=32 train images/sec (1 chip, bf16, ResNet-50)"
    if SMOKE:
        metric = "SMOKE harness self-test (tiny shapes, not a benchmark)"

    if best_name is None:
        print(json.dumps({
            "metric": metric,
            "value": 0.0, "unit": "images/sec", "vs_baseline": 0.0,
            "variants": results, "error": "all variants failed"}))
        sys.exit(1)

    profile_dir = os.environ.get("MINE_TPU_BENCH_PROFILE")
    if profile_dir:
        # re-run the winner in a fresh child with profiling enabled (the
        # sweep's children are gone; the compile cache makes this cheap)
        _, err, _ = _run_variant(best_name,
                                 {"MINE_TPU_BENCH_PROFILE": profile_dir})
        if err:
            print("profile re-run failed: %s" % err, file=sys.stderr)

    result = {
        "metric": metric,
        "value": round(best_ips, 3),
        "unit": "images/sec",
        # SMOKE throughput is meaningless against the real-config estimate
        "vs_baseline": None if SMOKE else round(
            best_ips / ESTIMATED_REFERENCE_IMAGES_PER_SEC, 3),
        # the denominator is an estimate with a documented spread — report
        # the multiplier at both edges, plus the value against the
        # reference's FLOPs-derived physical ceiling (BASELINE.md)
        "vs_baseline_range": None if SMOKE else [
            round(best_ips / REFERENCE_IMAGES_PER_SEC_SPREAD[1], 3),
            round(best_ips / REFERENCE_IMAGES_PER_SEC_SPREAD[0], 3)],
        "vs_reference_flops_ceiling": None if SMOKE else round(
            best_ips / REFERENCE_FLOPS_CEILING_IMAGES_PER_SEC, 3),
        "best_config": best_name,
        "variants": results,
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
