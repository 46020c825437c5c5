"""GPipe-style microbatched pipeline executor over the staged train step.

The fused train step (train/step.py _train_step_impl) is one XLA program;
past the single-slice regime its activation footprint is the binding
constraint (B=8 LLFF overflows a 16 GB v5e; round-2 notes in git
history). This module
schedules the step's four natural sub-programs — encoder, decoder,
warp/composite, fused loss (SynthesisTrainer.stage_encode/stage_decode/
stage_render/stage_loss) — as separately jitted stages over
`training.pipeline.microbatches` microbatches, with the stages placed on
contiguous sub-slices of the ("data", "plane") mesh when
`training.pipeline.stages` > 1 (MPMD over GSPMD sub-meshes: each stage is
still an SPMD program over its own slice rows).

Schedule: classic GPipe fill/drain. The fill phase runs every microbatch
through the forward chain (stage m+1's encoder overlaps stage m's decoder
via JAX async dispatch — the host only blocks when `time_stages` telemetry
is on); the drain phase walks microbatches in reverse through
loss-grad -> render-bwd -> decoder-bwd -> encoder-bwd, accumulating
gradients. Backward stages REMATERIALIZE their forward inside jax.vjp
(only the stage-boundary activations are held per microbatch, the GPipe
memory profile), so `training.remat` is ignored on this path — per-stage
recompute is inherent.

Numerics contract (pinned by tests/test_train_pipeline.py):
  * pipeline off (`training.pipeline.enabled=false`, the default): this
    module is never imported; the fused step is bitwise-untouched.
  * 1 stage x 1 microbatch: same RNG derivation as the fused step (fold_in
    step, split 3, full-batch disparity draw, one dropout key), same ghost-
    BN statistics threading, gradient accumulation mean over M=1 — matches
    fused params/metrics to house float tolerances (op order inside stages
    differs from the fused trace only by XLA fusion boundaries).
  * M microbatches: mean-of-per-microbatch grads/metrics with batch_stats
    threaded sequentially microbatch -> microbatch; matches a hand-
    accumulated per-microbatch reference.

Restrictions enforced here: mpi.num_bins_fine == 0 (coarse-to-fine
re-enters the model mid-render — no stage boundary), stages <= 4,
stages > 1 requires a mesh whose "data" axis the stage count divides, and
the global batch must divide by `microbatches`.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mine_tpu.config import PipelineConfig
from mine_tpu.parallel.mesh import DATA_AXIS, PLANE_AXIS

# the four sub-programs, in dataflow order; STAGE_MS_KEYS are the st1
# step-line keys (telemetry/stepline.py: appended keys, `stage_*_ms=` form)
STAGE_NAMES = ("encode", "decode", "render", "loss")
STAGE_MS_KEYS = tuple(f"stage_{n}_ms" for n in STAGE_NAMES) + (
    "stage_update_ms",)


def stage_assignment(stages: int) -> List[int]:
    """Contiguous partition of the 4 sub-programs into `stages` groups:
    assignment[i] = group index of sub-program i. np.array_split semantics
    (earlier groups take the extra program when 4 % stages != 0), matching
    tools/pipeline_plan.py's partition enumeration."""
    if not 1 <= stages <= len(STAGE_NAMES):
        raise ValueError(f"stages must be in [1, {len(STAGE_NAMES)}], "
                         f"got {stages}")
    out = [0] * len(STAGE_NAMES)
    for g, idxs in enumerate(np.array_split(np.arange(len(STAGE_NAMES)),
                                            stages)):
        for i in idxs:
            out[int(i)] = g
    return out


class PipelineExecutor:
    """Owns the per-stage jitted programs and runs one optimizer step as a
    microbatched fill/drain schedule. Constructed by SynthesisTrainer when
    `training.pipeline.enabled`; `step(state, batch)` is signature- and
    semantics-compatible with the fused jitted train step."""

    def __init__(self, trainer, pcfg: PipelineConfig,
                 time_stages: bool = True):
        if trainer.cfg.num_bins_fine > 0:
            raise ValueError(
                "training.pipeline.enabled requires mpi.num_bins_fine == 0: "
                "the coarse-to-fine refinement re-enters the model from "
                "inside the render and has no stage boundary "
                f"(got num_bins_fine={trainer.cfg.num_bins_fine})")
        self.trainer = trainer
        self.cfg = pcfg
        # host-side per-stage wall timing (block_until_ready around each
        # stage call -> serializes the async dispatch): telemetry for the
        # st1 stage_ms breakdown. Bench timing sweeps construct with
        # time_stages=False to measure the overlapped schedule.
        self.time_stages = time_stages
        self.last_stage_ms: Optional[Dict[str, float]] = None
        # test hook (tests/test_train_pipeline.py): keep the accumulated
        # gradient tree from the last step. Param comparisons alone can't
        # pin accumulation numerics — Adam flips update signs on
        # near-zero gradients — so the parity tests compare grads.
        self.keep_grads = False
        self.last_grads = None

        mesh = trainer.mesh
        self._assign = stage_assignment(pcfg.stages)
        if pcfg.stages > 1:
            if mesh is None:
                raise ValueError(
                    f"training.pipeline.stages={pcfg.stages} > 1 requires a "
                    "device mesh (stage placement slices the mesh's 'data' "
                    "axis); run with stages=1 on a single device")
            data = mesh.shape[DATA_AXIS]
            if data % pcfg.stages != 0:
                raise ValueError(
                    f"training.pipeline.stages={pcfg.stages} must divide "
                    f"the mesh 'data' axis ({data}) so every stage gets an "
                    "equal contiguous slice of device rows")
            rows = np.split(np.asarray(mesh.devices), pcfg.stages, axis=0)
            self._meshes = [Mesh(r, (DATA_AXIS, PLANE_AXIS)) for r in rows]
        elif mesh is not None:
            self._meshes = [mesh]
        else:
            self._meshes = None
        # explicit device_put between stages only when stages actually live
        # on different sub-meshes; at stages=1 everything already sits on
        # the (full) mesh / default device
        self._placement = mesh is not None and pcfg.stages > 1

        t = trainer
        # mesh handed to the render stage's constrain/shard_map sites: its
        # OWN sub-mesh (the fused step passes the full mesh here)
        rmesh = self._meshes[self._assign[2]] if self._meshes else None
        rmesh = rmesh if (rmesh is not None and rmesh.size > 1) else None

        # ---- forward programs (one jitted XLA program per stage) ----
        self._enc_fwd = jax.jit(t.stage_encode)
        self._dec_fwd = jax.jit(t.stage_decode)
        self._rend_fwd = jax.jit(
            lambda mpi, disp, mb: t.stage_render(mpi, disp, mb, mesh=rmesh))

        # ---- loss stage: forward + cotangent in one program ----
        def loss_vg(rendered, mb):
            (total, metrics), g_rendered = jax.value_and_grad(
                lambda r: t.stage_loss(r, mb), has_aux=True)(rendered)
            return total, metrics, g_rendered
        self._loss_vg = jax.jit(loss_vg)

        # ---- rematerializing backward programs ----
        # Each vjp recomputes its stage forward from the saved boundary
        # inputs; batch_stats are aux (non-differentiated), exactly like the
        # fused step's has_aux=True loss_fn.
        def enc_bwd(pb, sb, src_img, drop_key, g_feats):
            _, vjp_fn, _ = jax.vjp(
                lambda p: t.stage_encode(p, sb, src_img, drop_key),
                pb, has_aux=True)
            (g_pb,) = vjp_fn(g_feats)
            return g_pb
        self._enc_bwd = jax.jit(enc_bwd)

        def dec_bwd(pd, sd, feats, disp, drop_key, g_mpi):
            _, vjp_fn, _ = jax.vjp(
                lambda p, f: t.stage_decode(p, sd, f, disp, drop_key),
                pd, feats, has_aux=True)
            g_pd, g_feats = vjp_fn(g_mpi)
            return g_pd, g_feats
        self._dec_bwd = jax.jit(dec_bwd)

        def rend_bwd(mpi, disp, mb, g_rendered):
            _, vjp_fn = jax.vjp(
                lambda m: t.stage_render(m, disp, mb, mesh=rmesh), mpi)
            (g_mpi,) = vjp_fn(g_rendered)
            return g_mpi
        self._rend_bwd = jax.jit(rend_bwd)

        # ---- plane-content telemetry (training.layer_stats) ----
        # The fused step computes these inside the loss graph over the full
        # batch; here they get their own tiny program per microbatch and
        # average like every other scalar metric (alpha_std becomes a mean
        # of per-microbatch stds at M > 1 — telemetry-only drift, the
        # group-level stats in _apply_update are exact either way).
        if t.layer_stats:
            def plane_stats(mpi0):
                alpha = mpi0[:, :, 3].astype(jnp.float32)
                f32 = lambda c: jnp.mean(c.astype(jnp.float32))
                return {"layers/planes.alpha_mean": jnp.mean(alpha),
                        "layers/planes.alpha_std": jnp.std(alpha),
                        "layers/planes.alpha_sat_lo": f32(alpha < 0.01),
                        "layers/planes.alpha_sat_hi": f32(alpha > 0.99)}
            self._plane_stats = jax.jit(plane_stats)
        else:
            self._plane_stats = None

        # ---- optimizer update: the SAME body the fused step traces ----
        self._update = jax.jit(t._apply_update)

    # ---------------- placement helpers ----------------

    def _repl(self, prog: int):
        """Replicated sharding on sub-program `prog`'s stage mesh."""
        return NamedSharding(self._meshes[self._assign[prog]], P())

    def _put(self, tree, prog: int):
        """Move a (param/stat/activation/cotangent) pytree onto sub-program
        `prog`'s stage mesh, replicated. No-op unless stages > 1."""
        if not self._placement:
            return tree
        return jax.device_put(tree, self._repl(prog))

    def _put_batch(self, tree, prog: int, b: int):
        """Per-example pytree -> sub-program `prog`'s mesh, batch-sharded
        over its 'data' rows when the microbatch divides them (else
        replicated — correct, just not parallel)."""
        if not self._placement:
            return tree
        m = self._meshes[self._assign[prog]]
        spec = P(DATA_AXIS) if b % m.shape[DATA_AXIS] == 0 else P()
        return jax.device_put(tree, NamedSharding(m, spec))

    def _to_state_mesh(self, tree):
        """Stage-mesh pytree -> wherever the TrainState lives (replicated on
        the full mesh), for the update program's mixed-origin inputs."""
        if not self._placement:
            return tree
        return jax.device_put(
            tree, NamedSharding(self.trainer.mesh, P()))

    # ---------------- timing ----------------

    def _timed(self, acc: Dict[str, float], key: str, fn, *args):
        if not self.time_stages:
            return fn(*args)
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        acc[key] += (time.perf_counter() - t0) * 1e3
        return out

    # ---------------- one optimizer step ----------------

    def step(self, state, batch) -> Tuple[Any, Dict]:
        from mine_tpu.train.step import sample_disparity  # cycle-free at call
        t = self.trainer
        M = self.cfg.microbatches
        B = int(batch["src_img"].shape[0])
        if B % M != 0:
            raise ValueError(
                f"training.pipeline.microbatches={M} must divide the global "
                f"batch size ({B})")
        b = B // M
        ms = {k: 0.0 for k in STAGE_MS_KEYS}

        # RNG derivation mirrors the fused step exactly: fold the step into
        # the state key, split 3 (the fine key is unused — num_bins_fine==0
        # is enforced at construction), draw disparities at the FULL batch
        # size and slice rows per microbatch. One dropout key for all
        # microbatches, like the fused step's one key for the full batch.
        key = jax.random.fold_in(state.rng, state.step)
        d_key, _f_key, drop_key = jax.random.split(key, 3)
        disparity = sample_disparity(d_key, B, t.cfg)

        pb = self._put(state.params["backbone"], 0)
        pd = self._put(state.params["decoder"], 1)
        sb = state.batch_stats["backbone"]
        sd = state.batch_stats["decoder"]
        ek = self._put(drop_key, 0)
        dk = self._put(drop_key, 1)

        # ---- fill: forward every microbatch, keep boundary activations ----
        fwd = []
        for m in range(M):
            lo, hi = m * b, (m + 1) * b
            mb = {k: v[lo:hi] for k, v in batch.items()}
            disp = disparity[lo:hi]
            src = self._put_batch(mb["src_img"], 0, b)
            sb_in, sd_in = sb, sd  # ghost-BN: stats thread sequentially
            feats, sb = self._timed(ms, "stage_encode_ms",
                                    self._enc_fwd, pb, sb_in, src, ek)
            feats_d = self._put(feats, 1)
            disp_d = self._put_batch(disp, 1, b)
            mpi, sd = self._timed(ms, "stage_decode_ms",
                                  self._dec_fwd, pd, sd_in, feats_d, disp_d,
                                  dk)
            mpi_r = self._put(mpi, 2)
            disp_r = self._put_batch(disp, 2, b)
            mb_r = self._put_batch(mb, 2, b)
            rendered = self._timed(ms, "stage_render_ms",
                                   self._rend_fwd, mpi_r, disp_r, mb_r)
            fwd.append(dict(mb=mb, src=src, sb_in=sb_in, sd_in=sd_in,
                            feats=feats_d, disp=disp_d, mpi=mpi_r,
                            disp_r=disp_r, mb_r=mb_r, rendered=rendered))

        # ---- drain: loss grad + backward chain, last microbatch first ----
        grads_b = grads_d = metrics_sum = None
        for m in reversed(range(M)):
            a = fwd[m]
            rend_l = self._put(a["rendered"], 3)
            mb_l = self._put_batch(a["mb"], 3, b)
            _, metrics, g_rendered = self._timed(
                ms, "stage_loss_ms", self._loss_vg, rend_l, mb_l)
            if self._plane_stats is not None:
                metrics = dict(metrics, **self._timed(
                    ms, "stage_loss_ms", self._plane_stats, a["mpi"][0]))
            g_rendered = self._put(g_rendered, 2)
            g_mpi = self._timed(ms, "stage_render_ms", self._rend_bwd,
                                a["mpi"], a["disp_r"], a["mb_r"], g_rendered)
            g_mpi = self._put(g_mpi, 1)
            g_pd, g_feats = self._timed(ms, "stage_decode_ms", self._dec_bwd,
                                        pd, a["sd_in"], a["feats"], a["disp"],
                                        dk, g_mpi)
            g_feats = self._put(g_feats, 0)
            g_pb = self._timed(ms, "stage_encode_ms", self._enc_bwd,
                               pb, a["sb_in"], a["src"], ek, g_feats)
            add = lambda x, y: jax.tree_util.tree_map(jnp.add, x, y)
            grads_b = g_pb if grads_b is None else add(grads_b, g_pb)
            grads_d = g_pd if grads_d is None else add(grads_d, g_pd)
            metrics_sum = metrics if metrics_sum is None \
                else add(metrics_sum, metrics)
            fwd[m] = None  # release this microbatch's activations

        # mean over microbatches: grads match the fused full-batch gradient
        # (the loss is a mean over examples; equal microbatches make the
        # mean of per-microbatch grads the full-batch grad), metrics are
        # the same mean-of-means
        inv = 1.0 / M
        scale = lambda tree: jax.tree_util.tree_map(lambda x: x * inv, tree)
        grads = {"backbone": self._to_state_mesh(scale(grads_b)),
                 "decoder": self._to_state_mesh(scale(grads_d))}
        metrics = self._to_state_mesh(scale(metrics_sum))
        new_stats = {"backbone": self._to_state_mesh(sb),
                     "decoder": self._to_state_mesh(sd)}
        if self.keep_grads:
            self.last_grads = grads

        out = self._timed(ms, "stage_update_ms", self._update,
                          state, grads, metrics, new_stats)
        self.last_stage_ms = dict(ms) if self.time_stages else None
        return out
