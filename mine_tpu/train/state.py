"""Train state + optimizer.

Optimizer semantics match the reference (synthesis_task.py:83-87,116-118):
Adam with L2 weight decay folded into the gradient *before* the moment
updates (torch.optim.Adam's weight_decay), one parameter group per top-level
key of the parameter tree, each with its own learning rate `lr.<group>_lr`
(MINE: backbone and decoder; the looped language model: lm), and a
MultiStepLR schedule that decays them all by gamma at epoch milestones. A
family whose optimizer is another hands `Trainer` its own chain
(train/lm_step.py: clipped AdamW).

Unlike the reference's checkpoints — which drop step/epoch and RNG
(synthesis_task.py:629-631,650-652; SURVEY.md section 5) — the state carries
step and the PRNG key, so checkpoint/resume is exact.
"""

from __future__ import annotations

from typing import Any, Dict

import flax.struct
import jax
import jax.numpy as jnp
import optax


@flax.struct.dataclass
class TrainState:
    step: jnp.ndarray          # int32 scalar
    params: Any                # {group: subtree}; a group has its own lr
    batch_stats: Any
    opt_state: Any
    rng: jax.Array             # folded with step per training step
    guard: jax.Array           # int32 [3] non-finite-step-guard counters


# Indices into TrainState.guard — kept as one small device buffer (not
# separate fields) so the checkpoint layer can strip/inject it wholesale:
# on-disk checkpoints keep the stable 5-key tree and stay readable across
# guard changes, and the counters reset on restore (they are diagnostics
# of THIS run, not model state — see MIGRATION.md).
GUARD_SKIPPED = 0    # total steps skipped (non-finite loss/grad-norm)
GUARD_CONSEC = 1     # current run of consecutive skips (abort signal)
GUARD_LAST_BAD = 2   # state.step of the most recent skipped step, -1 never


def make_guard_buffer() -> jnp.ndarray:
    return jnp.asarray([0, 0, -1], jnp.int32)


def multistep_lr(base_lr: float, decay_epochs, gamma: float,
                 steps_per_epoch: int, accum: int = 1) -> optax.Schedule:
    """MultiStepLR: multiply by gamma at each epoch milestone.

    With gradient accumulation the schedule's clock is OPTIMIZER steps, so
    each epoch milestone is rounded from the micro-step product
    (e * steps_per_epoch // accum), not from a truncated per-epoch quotient
    — keeps the device schedule aligned with the host-side micro-step clock
    (current_lrs) even when accum does not divide steps_per_epoch. When
    several milestones land between the same two optimizer steps (accum >
    steps_per_epoch) their gammas compound on that one boundary."""
    boundaries: dict = {}
    for e in decay_epochs:
        b = int(e) * int(steps_per_epoch) // int(accum)
        boundaries[b] = boundaries.get(b, 1.0) * gamma
    return optax.piecewise_constant_schedule(base_lr, boundaries)


def lr_groups(config: Dict[str, Any]):
    """The parameter groups a configuration trains: every `lr.<group>_lr`
    key that holds a rate (params_default.yaml lists the key space; a
    configuration leaves the groups it does not have at null). They are
    the top-level keys of the parameter tree."""
    return tuple(k[len("lr."):-len("_lr")] for k in config
                 if k.startswith("lr.") and k.endswith("_lr")
                 and config[k] is not None)


def make_optimizer(config: Dict[str, Any], steps_per_epoch: int) -> optax.GradientTransformation:
    """Per-group Adam(+L2) with MultiStepLR: one group per `lr.<group>_lr`
    key (`lr_groups`), matching the reference's {backbone: lr.backbone_lr,
    decoder: lr.decoder_lr} and lr.weight_decay.

    training.grad_accum_steps > 1 wraps the whole thing in optax.MultiSteps
    (no reference equivalent — SURVEY.md section 2c "Gradient accumulation:
    NO"; added because one v5e chip caps the per-step batch at B<=4 at LLFF
    shapes, round-2 notes in git history): every micro-batch goes through the
    normal
    train_step, updates are emitted every k-th call with mean gradients,
    and state.step stays in micro-batch units everywhere (logging,
    checkpoint cadence, resume epoch math, current_lrs). The inner LR
    schedule ticks once per OPTIMIZER step, so its epoch boundaries are
    rescaled by 1/k to stay aligned with micro-step epochs. BN statistics
    remain per micro-batch (the standard accumulation trade)."""
    wd = float(config.get("lr.weight_decay", 0.0))
    gamma = float(config.get("lr.decay_gamma", 0.1))
    decay_epochs = config.get("lr.decay_steps", [])
    accum = int(config.get("training.grad_accum_steps", 1))
    assert accum >= 1, accum

    def group(base_lr: float) -> optax.GradientTransformation:
        return optax.chain(
            optax.add_decayed_weights(wd),
            optax.scale_by_adam(b1=0.9, b2=0.999, eps=1e-8),
            optax.scale_by_learning_rate(
                multistep_lr(base_lr, decay_epochs, gamma,
                             steps_per_epoch, accum=accum)),
        )

    def label_fn(params):
        return {k: k for k in params}  # top-level keys are the groups

    tx = optax.multi_transform(
        {g: group(float(config["lr.%s_lr" % g])) for g in lr_groups(config)},
        label_fn)
    if accum > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=accum)
    return tx


def new_train_state(params, batch_stats, tx, state_key) -> TrainState:
    """Step 0 of any model family: fresh optimizer state and guard."""
    return TrainState(step=jnp.zeros((), jnp.int32),
                      params=params,
                      batch_stats=batch_stats,
                      opt_state=tx.init(params),
                      rng=state_key,
                      guard=make_guard_buffer())


def create_train_state(model, config: Dict[str, Any], steps_per_epoch: int,
                       sample_img, sample_disparity, seed: int = 0) -> TrainState:
    """Initialize params/batch_stats and the optimizer state."""
    init_key, state_key = jax.random.split(jax.random.PRNGKey(seed))
    variables = model.init(init_key, sample_img, sample_disparity, train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    return new_train_state(params, batch_stats,
                           make_optimizer(config, steps_per_epoch), state_key)


def current_lrs(config: Dict[str, Any], steps_per_epoch: int, step: int):
    """Host-side LR readback for logging (reference logs encoder lr,
    synthesis_task.py:572). `step` is the micro-step clock (state.step);
    with grad accumulation the decay lands on the optimizer-step boundary
    e*spe//accum, which corresponds to micro-step (e*spe//accum)*accum —
    mirrored here so the logged LR always equals the applied one."""
    gamma = float(config.get("lr.decay_gamma", 0.1))
    decay_epochs = config.get("lr.decay_steps", [])
    accum = int(config.get("training.grad_accum_steps", 1))
    lrs = {}
    for name in lr_groups(config):
        lr = float(config["lr.%s_lr" % name])
        for e in decay_epochs:
            # piecewise_constant_schedule applies the scale for counts >=
            # boundary (empirically: sched(boundary) is already decayed);
            # the optimizer count at micro-step `step` is step // accum
            if step // accum >= int(e) * steps_per_epoch // accum:
                lr *= gamma
        lrs[name] = lr
    return lrs
