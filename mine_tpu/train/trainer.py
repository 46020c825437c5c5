"""What every model family's trainer shares: the jitted step's wiring, the
update, and the state's first program.

`Trainer` is the model-agnostic half of what `SynthesisTrainer` used to be in
one class: batch geometry over the mesh, `put_batch`, `init_state` (one
jitted program that takes the seed as its argument), the jit of the step
with its shardings and donation, `_apply_update` (Adam, the non-finite guard,
the per-group layer stats), `train_step` behind its `train.step.dispatch`
span, and the step's registration with telemetry/programs.py. A family's
trainer (train/step.py `SynthesisTrainer`, train/lm_step.py `LoopLMTrainer`)
adds its model, its `_init_state_impl` and the step function named by
`STEP_IMPL`, whose gradients and metrics it hands to `_apply_update`.
"""

from __future__ import annotations

import functools
import weakref
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from mine_tpu import telemetry
from mine_tpu.parallel import mesh as mesh_lib
from mine_tpu.testing import faults
from mine_tpu.train import resilience
from mine_tpu.train.state import (GUARD_CONSEC, GUARD_LAST_BAD, GUARD_SKIPPED,
                                  TrainState, make_optimizer)


def make_trainer(config: Dict[str, Any], mesh=None, steps_per_epoch: int = 1000,
                 **kwargs) -> "Trainer":
    """The trainer `model.family` selects. A family's modules are imported
    here and nowhere earlier: a MINE run imports none of the language
    model's, and the other way round."""
    family = config.get("model.family", "mine")
    if family == "mine":
        from mine_tpu.train.step import SynthesisTrainer
        return SynthesisTrainer(config, mesh=mesh,
                                steps_per_epoch=steps_per_epoch, **kwargs)
    if family == "looplm":
        from mine_tpu.train.lm_step import LoopLMTrainer
        return LoopLMTrainer(config, mesh=mesh,
                             steps_per_epoch=steps_per_epoch, **kwargs)
    raise ValueError(f"model.family must be mine|looplm, got {family!r}")


class Trainer:
    """Owns the optimizer and the jitted train step of one model family."""

    # the method `train_step` jits; its name is the step program's name in
    # the compiler, in a device trace and in telemetry/programs.py
    STEP_IMPL = "_train_step_impl"
    # what the loop's log line shows beside the loss: the meters it keeps,
    # and (label, parameter group) of the learning rate it prints
    METER_KEYS: Tuple[str, ...] = ("loss",)
    LOG_LR: Tuple[str, str] = ("lr", "")

    def __init__(self, config: Dict[str, Any], mesh=None,
                 steps_per_epoch: int = 1000,
                 compiler_options: Optional[Dict[str, Any]] = None,
                 tx: Optional[optax.GradientTransformation] = None):
        self.config = config
        self.mesh = mesh
        self.steps_per_epoch = steps_per_epoch
        self.grad_accum_steps = int(config.get("training.grad_accum_steps", 1))
        assert self.grad_accum_steps >= 1, self.grad_accum_steps
        # a family with an optimizer of its own hands it in; the groups and
        # rates `current_lrs` logs are the `lr.<group>_lr` keys either way
        self.tx = tx if tx is not None else make_optimizer(config,
                                                           steps_per_epoch)
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
        self._jit = functools.partial(
            jax.jit, compiler_options=compiler_options) \
            if compiler_options else jax.jit
        self._step_registered = False  # telemetry.programs has the step
        self._pipeline = None          # MINE's staged executor, where enabled

    def _jit_train_step(self):
        """The step function `STEP_IMPL` names, jitted: state donated and
        replicated, the batch sharded over the mesh's data axis."""
        # training.donate_batch: also donate the BATCH buffers to the train
        # step, so XLA reuses the staged input memory instead of holding
        # both the live batch and the step's workspace. Valid only when
        # every step gets a freshly staged batch (the async input pipeline,
        # train/loop.py + data/pipeline.py); callers that re-feed one
        # resident batch (bench.py's device-step variants, overfit tests)
        # must leave it off or the second call hits deleted buffers.
        donate = (0, 1) if bool(
            self.config.get("training.donate_batch", False)) else (0,)
        impl = getattr(self, self.STEP_IMPL)
        if self.mesh is None:
            return self._jit(impl, donate_argnums=donate)
        repl = mesh_lib.replicated(self.mesh)
        return self._jit(impl,
                         in_shardings=(repl,
                                       mesh_lib.batch_sharding(self.mesh)),
                         out_shardings=(repl, repl), donate_argnums=donate)

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

        def init(seed_i32):
            return self._init_state_impl(batch_size, seed_i32)

        # ONE compiled program, placed where the step wants its state. Run
        # op by op, a ResNet-50 init is ~700 small programs and the TPU's
        # compiler takes about a second for each (chip run, PR 24: 337 s).
        # The seed is the program's ARGUMENT, not a constant inside it: a
        # new seed is then no new program (25 s of compile a seed on the
        # v5e, chip run, PR 26).
        out = mesh_lib.replicated(self.mesh) if self.mesh is not None else None
        return jax.jit(init, out_shardings=out)(jnp.int32(seed))

    def _init_state_impl(self, batch_size: int, seed) -> TrainState:
        raise NotImplementedError

    # ---------------- the update ----------------

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

        telemetry.programs.register(self.STEP_IMPL, text_fn)

    # ---------------- what the loop logs ----------------

    def log_summary(self, m: Dict[str, float]) -> str:
        """The family's lines of the loop's log record, from the metrics
        read back at log cadence."""
        return ""

    def log_gauges(self, m: Dict[str, float],
                   times: Dict[str, float]) -> Dict[str, float]:
        """{registry gauge name: value} the loop sets at log cadence, from
        values it has already read back (no new host sync)."""
        return {}
