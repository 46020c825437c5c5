"""Jitted train/eval steps over a device mesh.

One `train_step` = forward (encoder + disparity-conditioned decoder, with
optional coarse-to-fine), all 4 loss scales, backward, and the two-group Adam
update — a single XLA program (the reference runs this as separate eager
stages, synthesis_task.py:604-615). Data parallelism is the sharded batch
axis; the gradient all-reduce the reference got from DDP and the SyncBN
statistics both fall out of GSPMD on the ("data", "plane") mesh.

RNG: the reference samples disparities with unseeded global RNG per step
(rendering_utils.py:86); here every step folds the state's PRNG key with the
step counter — reproducible and resumable by construction.
"""

from __future__ import annotations

import functools
import weakref
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from mine_tpu import geometry, telemetry
from mine_tpu.config import (MPIConfig, mpi_config_from_dict,
                             pipeline_config_from_dict,
                             validate_model_shapes)
from mine_tpu.models.mpi import MPIPredictor
from mine_tpu.ops import rendering, sampling
from mine_tpu.parallel import mesh as mesh_lib
from mine_tpu.testing import faults
from mine_tpu.train import resilience
from mine_tpu.train.loss import (compute_losses, loss_from_rendered,
                                 render_all_scales)
from mine_tpu.train.state import (GUARD_CONSEC, GUARD_LAST_BAD, GUARD_SKIPPED,
                                  TrainState, create_train_state,
                                  make_optimizer)


def _remat_policy(value):
    """training.remat -> (enabled, jax.checkpoint policy).

    false/"none": no remat; true/"full": save nothing (recompute the whole
    model forward in backward); "dots": save MXU results (recompute only
    elementwise work — the usual TPU sweet spot); "dots_no_batch": the
    variant excluding batch dims (finer-grained memory saving).
    """
    if value in (False, None, "none", "false"):
        return False, None
    if value in (True, "full", "true"):
        return True, None  # jax.checkpoint default: save nothing
    policies = {
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch":
            jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    }
    if value not in policies:
        raise ValueError(
            f"training.remat must be false|true|dots|dots_no_batch, "
            f"got {value!r}")
    return True, policies[value]


def sample_disparity(key: jax.Array, batch_size: int, cfg: MPIConfig) -> jnp.ndarray:
    """Coarse plane disparities for one step (synthesis_task._get_disparity_list
    :31-60): stratified per-bin samples, explicit bin edges when provided,
    or a fixed linspace when mpi.fix_disparity."""
    S = cfg.num_bins_coarse
    has_list = len(cfg.disparity_list) == S + 1
    if cfg.fix_disparity:
        if has_list:
            d = jnp.asarray(cfg.disparity_list[1:], jnp.float32)
            return jnp.broadcast_to(d[None], (batch_size, S))
        return sampling.fixed_disparity_linspace(
            batch_size, S, cfg.disparity_start, cfg.disparity_end)
    if has_list:
        return sampling.uniformly_sample_disparity_from_bins(
            key, batch_size, np.asarray(cfg.disparity_list, np.float32))
    return sampling.uniformly_sample_disparity_from_linspace_bins(
        key, batch_size, S, cfg.disparity_start, cfg.disparity_end)


class SynthesisTrainer:
    """Owns the model + optimizer and builds the jitted step functions.

    The reference's SynthesisTask god-object (synthesis_task.py:63-670) is
    split: this class is the step compiler; the host loop (logging, eval
    cadence, checkpointing) lives in mine_tpu.train.loop.
    """

    def __init__(self, config: Dict[str, Any],
                 mesh=None,
                 steps_per_epoch: int = 1000,
                 lpips_params=None,
                 compiler_options: Optional[Dict[str, Any]] = None):
        self.config = config
        self.cfg = mpi_config_from_dict(config)
        self.mesh = mesh
        self.steps_per_epoch = steps_per_epoch
        validate_model_shapes(self.cfg)

        # Pallas backends compose with multi-device meshes via shard_map
        # (ops/rendering.py, ops/warp.py): warp splits B*S over data*plane,
        # composite batches over "data" with the plane axis gathered.

        dtype_name = config.get("training.dtype", "bfloat16")
        dtype = {"bfloat16": jnp.bfloat16, "float32": None}[dtype_name]
        self.model = MPIPredictor(
            num_layers=self.cfg.num_layers,
            pos_encoding_multires=self.cfg.pos_encoding_multires,
            use_alpha=self.cfg.use_alpha,
            sigma_dropout_rate=self.cfg.sigma_dropout_rate,
            dtype=dtype,
            mesh=mesh if (mesh is not None and mesh.size > 1) else None,
            plane_chunks=int(config.get("training.decoder_plane_chunks", 1)),
            decoder_variant=str(config.get("model.decoder_variant",
                                           "reference")))
        chunks = self.model.plane_chunks
        if chunks > 1:
            # fail at construction, not as a silent unchunked (full-B*S HBM)
            # run or an opaque GSPMD sharding error on the chip — the r2
            # grant wedge was exactly that footprint
            if self.cfg.num_bins_coarse % chunks != 0:
                raise ValueError(
                    f"training.decoder_plane_chunks={chunks} must divide "
                    f"mpi.num_bins_coarse={self.cfg.num_bins_coarse}")
            plane = mesh.shape.get(mesh_lib.PLANE_AXIS, 1) if mesh else 1
            if plane > 1 and (self.cfg.num_bins_coarse // chunks) % plane:
                raise ValueError(
                    f"chunk size {self.cfg.num_bins_coarse // chunks} "
                    f"(= mpi.num_bins_coarse/{chunks}) must be divisible "
                    f"by the mesh plane axis ({plane}) so each chunk's "
                    f"B*S block still shards over ('data','plane')")
        self.remat, self.remat_policy = _remat_policy(
            config.get("training.remat", False))
        self.grad_accum_steps = int(config.get("training.grad_accum_steps", 1))
        assert self.grad_accum_steps >= 1, self.grad_accum_steps
        self.tx = make_optimizer(config, steps_per_epoch)
        self.lpips_params = lpips_params
        # Non-finite step guard (training.guard_nonfinite, default on): the
        # all-finite check and zero-update swap are traced INTO the step —
        # no extra host sync, guard counters ride in TrainState.guard and
        # surface through the (already log-cadence-synced) metrics.
        self.guard_nonfinite = bool(config.get("training.guard_nonfinite",
                                               True))
        # Per-layer-group training telemetry (training.layer_stats, default
        # off): per-group grad norms, update-to-weight ratios, and plane
        # alpha distribution summaries, computed INSIDE the jitted step as
        # scalar metrics. They ride the existing log-cadence metrics
        # readback — zero additional host syncs (the transfer_guard audit
        # pass runs with this enabled), and no new dot_generals (norms and
        # moments are elementwise + reductions), so dot budgets are
        # unchanged.
        self.layer_stats = bool(config.get("training.layer_stats", False))
        # Fault injection is resolved at TRACE time (set the plan before
        # constructing the trainer): None in production, so the injected
        # jnp.where never enters the compiled program.
        self._nan_grad_window = faults.nan_grad_window()

        # compiler_options reach every jitted step — the multichip dry run
        # certifies CORRECTNESS of the sharded programs on a single-core
        # CPU host and passes xla_backend_optimization_level=0 there (the
        # SPMD partitioner and numerics are unaffected; only backend
        # codegen effort drops, ~2.3x faster compiles). None for training.
        jit = functools.partial(jax.jit, compiler_options=compiler_options) \
            if compiler_options else jax.jit
        # training.donate_batch: also donate the BATCH buffers to the train
        # step, so XLA reuses the staged input memory instead of holding
        # both the live batch and the step's workspace. Valid only when
        # every step gets a freshly staged batch (the async input pipeline,
        # train/loop.py + data/pipeline.py); callers that re-feed one
        # resident batch (bench.py's device-step variants, overfit tests)
        # must leave it off or the second call hits deleted buffers.
        donate_train = (0, 1) if bool(
            config.get("training.donate_batch", False)) else (0,)
        if mesh is not None:
            batch_s = mesh_lib.batch_sharding(mesh)
            repl = mesh_lib.replicated(mesh)
            self._train_step = jit(self._train_step_impl,
                                   in_shardings=(repl, batch_s),
                                   out_shardings=(repl, repl),
                                   donate_argnums=donate_train)
            self._eval_step = jit(self._eval_step_impl,
                                  in_shardings=(repl, batch_s, repl),
                                  out_shardings=repl)
            # padded remainder batches: same collective shape as _eval_step
            # plus a [B] 0/1 validity weight sharded with the batch — every
            # host participates (lockstep) and padding examples are excluded
            # exactly from the weighted metric means
            self._eval_step_masked = jit(
                self._eval_step_masked_impl,
                in_shardings=(repl, batch_s, repl, batch_s),
                out_shardings=repl)
        else:
            self._train_step = jit(self._train_step_impl,
                                   donate_argnums=donate_train)
            self._eval_step = jit(self._eval_step_impl)
            self._eval_step_masked = jit(self._eval_step_masked_impl)
        # Encode-once eval (serve.eval_encode_once, train/loop.py run_eval):
        # the eval step split into its two halves so the host loop can cache
        # the encode per DISTINCT source image (serve.PyramidCache) and pay
        # only the loss/render half per (src, tgt) pair. Gated to
        # single-host in the loop; plain jit suffices on mesh>1 too (GSPMD
        # reshards the replicated-state inputs on the fly).
        self._eval_encode = jit(self._eval_encode_impl)
        self._eval_encode_c2f = jit(self._eval_encode_c2f_impl,
                                    static_argnames=("batch_size",))
        self._eval_losses = jit(self._eval_losses_impl)
        self._eval_losses_masked = jit(self._eval_losses_masked_impl)

        # Pipeline-staged training (training.pipeline.*, default off):
        # enabled routes train_step through the staged GPipe-style executor
        # (mine_tpu/parallel/pipeline.py). With enabled=False nothing is
        # constructed and the fused jitted step above runs untouched —
        # bitwise-identical outputs, same-compiled program.
        self.pipeline_cfg = pipeline_config_from_dict(config)
        self._step_registered = False  # telemetry.programs has the step
        self._pipeline = None
        if self.pipeline_cfg.enabled:
            from mine_tpu.parallel.pipeline import PipelineExecutor
            self._pipeline = PipelineExecutor(self, self.pipeline_cfg)

    # ---------------- batch geometry ----------------

    def global_batch_size(self) -> int:
        """data.per_gpu_batch_size is per *device on the data axis* (the
        reference's per-GPU batch, train.py:84); the jitted step sees the
        global batch."""
        per_device = int(self.config.get("data.per_gpu_batch_size", 2))
        data_size = self.mesh.shape[mesh_lib.DATA_AXIS] if self.mesh else 1
        return per_device * data_size

    def local_batch_size(self) -> int:
        """Examples each host must feed per step."""
        assert self.global_batch_size() % jax.process_count() == 0
        return self.global_batch_size() // jax.process_count()

    def put_batch(self, np_batch):
        """Host batch -> (possibly multi-host global) device batch, committed
        under the mesh's input sharding (parallel/mesh.put_batch) so the
        jitted step consumes it without a reshard. Called by the train
        loop's DeviceStager from a background thread — keep it free of
        trainer state mutation."""
        return mesh_lib.put_batch(np_batch, self.mesh)

    # ---------------- state ----------------

    def init_state(self, batch_size: int, seed: Optional[int] = None) -> TrainState:
        if seed is None:
            seed = int(self.config.get("training.seed", 0))
        H, W = self.cfg.img_h, self.cfg.img_w

        def init():
            img = jnp.zeros((batch_size, H, W, 3), jnp.float32)
            disp = jnp.full((batch_size, self.cfg.num_bins_total), 0.5,
                            jnp.float32)
            return create_train_state(self.model, self.config,
                                      self.steps_per_epoch, img, disp,
                                      seed=seed)

        # ONE compiled program, placed where the step wants its state. Run
        # op by op, a ResNet-50 init is ~700 small programs and the TPU's
        # compiler takes about a second for each (chip run, PR 24: 337 s).
        out = mesh_lib.replicated(self.mesh) if self.mesh is not None else None
        return jax.jit(init, out_shardings=out)()

    # ---------------- forward ----------------

    def _apply_model(self, params, batch_stats, img, disparity, train, drop_key):
        variables = {"params": params, "batch_stats": batch_stats}
        if self.remat and train:
            apply = jax.checkpoint(
                lambda v, i, d: self.model.apply(
                    v, i, d, train=True, mutable=["batch_stats"],
                    rngs={"dropout": drop_key}),
                policy=self.remat_policy)
            return apply(variables, img, disparity)
        if train:
            return self.model.apply(variables, img, disparity, train=True,
                                    mutable=["batch_stats"],
                                    rngs={"dropout": drop_key})
        return self.model.apply(variables, img, disparity, train=False), None

    def _forward(self, params, batch_stats, batch, disparity, fine_key,
                 drop_key, train: bool):
        """Model forward incl. optional coarse-to-fine plane refinement."""
        state = {"bs": batch_stats}

        def predictor(img, disp):
            out, mutated = self._apply_model(params, state["bs"], img, disp,
                                             train, drop_key)
            if mutated is not None:
                state["bs"] = mutated["batch_stats"]
            return out

        if self.cfg.num_bins_fine > 0:
            H, W = batch["src_img"].shape[1:3]
            grid = geometry.cached_pixel_grid(H, W)
            K_src_inv = geometry.inverse_intrinsics(batch["K_src"])
            xyz_coarse = geometry.plane_xyz_src(grid, disparity, K_src_inv)
        else:
            xyz_coarse = None
        mpi_list, disparity_all = rendering.predict_mpi_coarse_to_fine(
            predictor, fine_key, batch["src_img"], xyz_coarse, disparity,
            self.cfg.num_bins_fine, self.cfg.is_bg_depth_inf)
        return mpi_list, disparity_all, state["bs"]

    # ---------------- steps ----------------

    def _grads_and_metrics(self, state: TrainState, batch, key):
        """One micro-batch's (grads, metrics, new_batch_stats)."""
        d_key, f_key, drop_key = jax.random.split(key, 3)
        B = batch["src_img"].shape[0]
        disparity = sample_disparity(d_key, B, self.cfg)

        def loss_fn(params):
            mpi_list, disparity_all, new_stats = self._forward(
                params, state.batch_stats, batch, disparity, f_key, drop_key,
                train=True)
            total, metrics, _ = compute_losses(
                mpi_list, disparity_all, batch, self.cfg, mesh=self.mesh)
            if self.layer_stats:
                # plane content health at the full-resolution scale: alpha
                # collapse (everything transparent/opaque) is the classic
                # silent MPI failure mode — [B,S,4,h,w], channel 3 = alpha.
                # optimization_barrier keeps the stat reductions from
                # CSE/fusing with the loss graph: the numeric step must be
                # bitwise-identical with layer_stats on or off
                with jax.named_scope("layer_stats_planes"):
                    # stop_gradient lowers the AD tracer to its primal
                    # (optimization_barrier has no differentiation rule)
                    mpi0 = jax.lax.optimization_barrier(
                        jax.lax.stop_gradient(mpi_list[0]))
                    alpha = mpi0[:, :, 3].astype(jnp.float32)
                    metrics = dict(
                        metrics,
                        **{"layers/planes.alpha_mean": jnp.mean(alpha),
                           "layers/planes.alpha_std": jnp.std(alpha),
                           "layers/planes.alpha_sat_lo":
                               jnp.mean((alpha < 0.01).astype(jnp.float32)),
                           "layers/planes.alpha_sat_hi":
                               jnp.mean((alpha > 0.99).astype(jnp.float32))})
            return total, (metrics, new_stats)

        (_, (metrics, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        return grads, metrics, new_stats

    # ---------------- staged sub-programs (pipeline path) ----------------
    # The fused step above, cut at its natural seams: encoder -> decoder ->
    # warp/composite -> fused loss. Each is a pure function of explicit
    # param/stat subtrees, so the pipeline executor
    # (mine_tpu/parallel/pipeline.py) can jit, place, and differentiate
    # them independently, and analysis/programs.py registers each with its
    # own dot/cost baseline row. Restricted to mpi.num_bins_fine == 0 (the
    # coarse-to-fine refinement re-enters the model mid-render and has no
    # stage boundary); the executor enforces that.

    def stage_encode(self, backbone_params, backbone_stats, src_img,
                     drop_key):
        """Encoder stage: src images -> backbone feature pyramid.
        Returns (feats, new_backbone_stats). Flax resolves the partial
        {"backbone": ...} subtrees lazily, so only the backbone's
        params/stats ever live on this stage's devices."""
        feats, mut = self.model.apply(
            {"params": {"backbone": backbone_params},
             "batch_stats": {"backbone": backbone_stats}},
            src_img, True, method="encode", mutable=["batch_stats"],
            rngs={"dropout": drop_key})
        return feats, mut["batch_stats"]["backbone"]

    def stage_decode(self, decoder_params, decoder_stats, feats, disparity,
                     drop_key):
        """Decoder stage: feature pyramid + disparity -> 4-scale MPI list.
        Returns (mpi_list, new_decoder_stats). The dropout rng folds the
        same module path as the fused apply, so sigma-dropout masks match
        the fused step exactly."""
        mpi_list, mut = self.model.apply(
            {"params": {"decoder": decoder_params},
             "batch_stats": {"decoder": decoder_stats}},
            list(feats), disparity, True, method="decode",
            mutable=["batch_stats"], rngs={"dropout": drop_key})
        return mpi_list, mut["batch_stats"]["decoder"]

    def stage_render(self, mpi_list, disparity, batch, mesh=None):
        """Warp/composite stage: the render half of all 4 loss scales
        (train/loss.render_all_scales) -> list of per-scale rendered
        pytrees, the boundary the loss stage's cotangent flows back
        through."""
        return render_all_scales(mpi_list, disparity, batch, self.cfg,
                                 mesh=mesh)

    def stage_loss(self, rendered, batch):
        """Fused-loss stage: loss terms + cross-scale aggregation over the
        rendered pytrees -> (total, metrics)."""
        total, metrics, _ = loss_from_rendered(rendered, batch, self.cfg)
        return total, metrics

    def _train_step_impl(self, state: TrainState, batch) -> Tuple[TrainState, Dict]:
        key = jax.random.fold_in(state.rng, state.step)
        grads, metrics, new_stats = self._grads_and_metrics(state, batch, key)
        return self._apply_update(state, grads, metrics, new_stats)

    def _apply_update(self, state: TrainState, grads, metrics,
                      new_stats) -> Tuple[TrainState, Dict]:
        """Optimizer update + non-finite guard + layer telemetry over
        already-computed (possibly pipeline-accumulated) gradients. The
        fused step traces this inline; the pipeline executor jits it as its
        own update program — one body, so both paths apply the identical
        update/guard/metrics semantics."""
        if self._nan_grad_window is not None:
            # chaos-test seam: poison the gradients at the planned step(s);
            # absent a plan this branch is not traced at all
            at_step, from_step = self._nan_grad_window
            poison = jnp.zeros((), bool)
            if at_step >= 0:
                poison |= state.step == at_step
            if from_step >= 0:
                poison |= state.step >= from_step
            grads = jax.tree_util.tree_map(
                lambda g: jnp.where(poison, jnp.asarray(jnp.nan, g.dtype), g),
                grads)
        with jax.named_scope("adam_update"):
            updates, new_opt_state = self.tx.update(grads, state.opt_state,
                                                    state.params)
            new_params = optax.apply_updates(state.params, updates)
        guard = state.guard
        if self.guard_nonfinite:
            with jax.named_scope("nonfinite_guard"):
                gnorm = optax.global_norm(grads)
                ok = jnp.isfinite(metrics["loss"]) & jnp.isfinite(gnorm)
                # poisoned step -> zero-update: keep the old params /
                # opt_state / batch_stats (step still advances, so the RNG
                # stream and cadences stay aligned with an unpoisoned run)
                new_params = resilience.select_tree(ok, new_params,
                                                    state.params)
                new_opt_state = resilience.select_tree(ok, new_opt_state,
                                                       state.opt_state)
                new_stats = resilience.select_tree(ok, new_stats,
                                                   state.batch_stats)
                bad = (~ok).astype(jnp.int32)
                skipped = state.guard[GUARD_SKIPPED] + bad
                consec = (state.guard[GUARD_CONSEC] + bad) * bad
                last_bad = jnp.where(ok, state.guard[GUARD_LAST_BAD],
                                     state.step.astype(jnp.int32))
                guard = jnp.stack([skipped, consec, last_bad])
                metrics = dict(metrics,
                               grad_norm=gnorm,
                               skipped_steps=skipped,
                               guard_consecutive=consec,
                               guard_last_bad_step=last_bad)
        if self.layer_stats:
            # per-top-level-group (backbone / decoder) optimization health:
            # grad norm, and the update-to-weight ratio that flags a group
            # whose effective learning rate has gone degenerate. Scalars
            # only — they merge into the metrics dict and reach the host
            # exclusively through the log-cadence readback. Placement is
            # deliberate: the numeric step must be bitwise-identical with
            # layer_stats on or off, so the norms only touch values that
            # are materialized either way — grads (whose per-leaf square
            # sums CSE with the nonfinite guard's global norm), the input
            # params, and the POST-guard new_params that the step returns.
            # Consuming the optax `updates` tree (or the pre-guard
            # new_params) re-fuses the adam update and drifts a leaf, so
            # the applied-update norm is taken as ||new - old|| instead —
            # which also truthfully reads 0 on a guard-skipped step.
            with jax.named_scope("layer_stats_groups"):
                layer_metrics = {}
                for group in state.params:
                    gn = optax.global_norm(grads[group])
                    un = optax.global_norm(jax.tree_util.tree_map(
                        lambda n, o: n - o, new_params[group],
                        state.params[group]))
                    wn = optax.global_norm(state.params[group])
                    layer_metrics[f"layers/{group}.grad_norm"] = gn
                    layer_metrics[f"layers/{group}.param_norm"] = wn
                    layer_metrics[f"layers/{group}.update_ratio"] = \
                        un / (wn + 1e-12)
                metrics = dict(metrics, **layer_metrics)
        new_state = TrainState(step=state.step + 1,
                               params=new_params,
                               batch_stats=new_stats,
                               opt_state=new_opt_state,
                               rng=state.rng,
                               guard=guard)
        return new_state, metrics

    def _eval_step_impl(self, state: TrainState, batch, eval_key,
                        example_weight=None):
        """Validation step: eval-mode BN, LPIPS at scale 0 when weights are
        available (synthesis_task.py:341-344,476-507)."""
        d_key, f_key = jax.random.split(eval_key)
        B = batch["src_img"].shape[0]
        disparity = sample_disparity(d_key, B, self.cfg)
        mpi_list, disparity_all, _ = self._forward(
            state.params, state.batch_stats, batch, disparity, f_key, None,
            train=False)
        _, metrics, visuals = compute_losses(
            mpi_list, disparity_all, batch, self.cfg, mesh=self.mesh,
            is_val=True, lpips_params=self.lpips_params,
            example_weight=example_weight)
        return metrics, visuals

    def _eval_step_masked_impl(self, state: TrainState, batch, eval_key,
                               example_weight):
        metrics, _ = self._eval_step_impl(state, batch, eval_key,
                                          example_weight)
        return metrics

    def _eval_encode_impl(self, state: TrainState, src_img, disparity):
        """Encode half of the eval step: model forward only (eval-mode BN,
        no coarse-to-fine). Returns the 4-scale MPI pyramid. Configs with
        mpi.num_bins_fine > 0 go through _eval_encode_c2f_impl instead."""
        return self.model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            src_img, disparity, train=False)

    def _eval_encode_c2f_impl(self, state: TrainState, src_img, disparity,
                              fine_key, row, K_src, batch_size: int):
        """Coarse-to-fine encode half for ONE example of a fused eval batch.

        Replays exactly the fine-plane draws the fused _eval_step_impl makes
        for batch row `row`: the uniforms behind sample_pdf are drawn at the
        FULL eval-batch shape (`batch_size` static) from `fine_key` and this
        example's row is sliced out (rendering.predict_mpi_coarse_to_fine
        fine_rows=...), so per-example encode-once metrics match the fused
        batch bit-for-bit in the sampling and to float tolerance overall.
        Returns (mpi_list, disparity_all) — both cacheable per src image.
        """
        def predictor(img, disp):
            return self.model.apply(
                {"params": state.params, "batch_stats": state.batch_stats},
                img, disp, train=False)

        H, W = src_img.shape[1:3]
        grid = geometry.cached_pixel_grid(H, W)
        xyz_coarse = geometry.plane_xyz_src(
            grid, disparity, geometry.inverse_intrinsics(K_src))
        return rendering.predict_mpi_coarse_to_fine(
            predictor, fine_key, src_img, xyz_coarse, disparity,
            self.cfg.num_bins_fine, self.cfg.is_bg_depth_inf,
            fine_rows=(batch_size, row))

    def _eval_losses_impl(self, state: TrainState, mpi_list, disparity_all,
                          batch, example_weight=None):
        """Render+loss half of the eval step, fed a (possibly cache-replayed)
        MPI pyramid instead of re-running the encoder."""
        del state  # same call signature family as the other eval steps
        _, metrics, visuals = compute_losses(
            mpi_list, disparity_all, batch, self.cfg, mesh=self.mesh,
            is_val=True, lpips_params=self.lpips_params,
            example_weight=example_weight)
        return metrics, visuals

    def _eval_losses_masked_impl(self, state: TrainState, mpi_list,
                                 disparity_all, batch, example_weight):
        metrics, _ = self._eval_losses_impl(state, mpi_list, disparity_all,
                                            batch, example_weight)
        return metrics

    # ---------------- public API ----------------

    def train_step(self, state: TrainState, batch) -> Tuple[TrainState, Dict]:
        with telemetry.span("train.step.dispatch"):
            if self._pipeline is not None:
                return self._pipeline.step(state, batch)
            if not self._step_registered:
                self._register_step_program(state, batch)
            return self._train_step(state, batch)

    def _register_step_program(self, state: TrainState, batch) -> None:
        """Remember the first call's avals and shardings, and tell
        telemetry/programs.py how to get the step's optimized HLO text from
        them (instruction name -> layer, for readers of a device trace).
        Lazy: nothing is lowered unless `programs.layers` is asked, after
        the run; the compile it then makes is the one this call makes, so
        the compile caches have it."""
        self._step_registered = True

        def aval(x):
            # a sharding only where the array is committed to it: an aval
            # that commits an uncommitted argument lowers to another
            # module, and compiles again (chip run, PR 28: 116 s)
            sharding = x.sharding if getattr(x, "committed", False) else None
            return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                        sharding=sharding)

        avals = jax.tree_util.tree_map(aval, (state, batch))
        trainer = weakref.ref(self)

        def text_fn() -> str:
            me = trainer()
            if me is None:
                return ""
            return me._train_step.lower(*avals).compile().as_text()

        telemetry.programs.register(self._train_step_impl.__name__, text_fn)

    def eval_step(self, state: TrainState, batch, eval_key):
        return self._eval_step(state, batch, eval_key)

    def eval_step_masked(self, state: TrainState, batch, eval_key,
                         example_weight):
        """Collective eval for padded remainder batches: `example_weight`
        [global_B] is 1 for real examples, 0 for padding; metrics come back
        as weighted means over the real examples only (no dropped val
        examples on any host count — VERDICT r2 weak item 4)."""
        return self._eval_step_masked(state, batch, eval_key, example_weight)

    def eval_encode(self, state: TrainState, src_img, disparity):
        """[B,H,W,3] src + [B,S] disparity -> 4-scale MPI pyramid (list of
        [B,S,4,h,w]); the cacheable half of the encode-once eval path."""
        return self._eval_encode(state, src_img, disparity)

    def eval_encode_c2f(self, state: TrainState, src_img, disparity,
                        fine_key, row, K_src, batch_size: int):
        """Coarse-to-fine encode of eval-batch row `row` (1-example inputs;
        `batch_size` is the FULL fused batch size, static). Returns
        (mpi_list, disparity_all) matching the fused eval step's fine-plane
        RNG for that row — the encode-once path for num_bins_fine > 0."""
        return self._eval_encode_c2f(state, src_img, disparity, fine_key,
                                     jnp.asarray(row, jnp.int32), K_src,
                                     batch_size=batch_size)

    def eval_losses(self, state: TrainState, mpi_list, disparity_all, batch):
        return self._eval_losses(state, mpi_list, disparity_all, batch)

    def eval_losses_masked(self, state: TrainState, mpi_list, disparity_all,
                           batch, example_weight):
        return self._eval_losses_masked(state, mpi_list, disparity_all,
                                        batch, example_weight)

    def put_example_array(self, v):
        """[local_B,...] host array -> global batch-sharded device array."""
        if self.mesh is None or jax.process_count() == 1:
            return jnp.asarray(v)
        return jax.make_array_from_process_local_data(
            mesh_lib.batch_sharding(self.mesh), v)
