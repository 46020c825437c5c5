"""The looped language model's trainer: `Trainer`'s update and wiring round
models/looplm.py and train/lm_loss.py.

One `train_step` = embedding, `total_ut_steps` passes over the weight-tied
stack with the head and the cross entropy inside each pass, the expected
loss over the exit distribution, backward, clipped AdamW: a single XLA
program, registered with telemetry/programs.py under its own name.

A batch is {"tokens", "labels", "mask"}, each [rows, seq_len]
(data/tokens.py): labels are the next tokens, mask the slots that hold one.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

from mine_tpu.kernels import on_tpu_backend
from mine_tpu.models import looplm
from mine_tpu.train import lm_loss
from mine_tpu.train.state import TrainState, multistep_lr, new_train_state
from mine_tpu.train.trainer import Trainer

# AdamW as the family trains; the source config.json states no optimizer, so
# these are listed under `assumed` (benchmark/configs/ouro_2.6b_d8.json). The
# rate and the decay are the shared keys `lr.lm_lr` and `lr.weight_decay`.
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
CLIP_GLOBAL_NORM = 1.0


def _decays(params):
    """AdamW's decay is for the matrices: not the norms' scales, not the
    gate's bias (the family's convention; stated under `assumed`)."""
    def matrix(path, _):
        name = str(getattr(path[-1], "key", path[-1]))
        return not (name.startswith("norm") or name in ("final_norm", "b"))
    return jax.tree_util.tree_map_with_path(matrix, params)


def clipped_adamw(config: Dict[str, Any],
                  steps_per_epoch: int) -> optax.GradientTransformation:
    """Clip the gradients' global norm, Adam's moments, the decoupled decay
    on the matrices, the rate on its MultiStepLR schedule."""
    accum = int(config.get("training.grad_accum_steps", 1))
    tx = optax.chain(
        optax.clip_by_global_norm(CLIP_GLOBAL_NORM),
        optax.scale_by_adam(b1=ADAM_B1, b2=ADAM_B2, eps=ADAM_EPS),
        optax.add_decayed_weights(float(config["lr.weight_decay"]),
                                  mask=_decays),
        optax.scale_by_learning_rate(multistep_lr(
            float(config["lr.lm_lr"]), config.get("lr.decay_steps", []),
            float(config.get("lr.decay_gamma", 0.1)), steps_per_epoch,
            accum=accum)))
    return optax.MultiSteps(tx, every_k_schedule=accum) if accum > 1 else tx


class LoopLMTrainer(Trainer):
    STEP_IMPL = "_lm_train_step_impl"
    LOG_LR = ("lm_lr", "lm")

    def __init__(self, config: Dict[str, Any], mesh=None,
                 steps_per_epoch: int = 1000, compiler_options=None):
        super().__init__(config, mesh=mesh, steps_per_epoch=steps_per_epoch,
                         compiler_options=compiler_options,
                         tx=clipped_adamw(config, steps_per_epoch))
        self.cfg = looplm.looplm_config_from_dict(config)
        self.seq_len = int(config["data.seq_len"])
        self.dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            config.get("training.dtype", "bfloat16")]
        if mesh is not None and mesh.size > 1 and on_tpu_backend():
            # the attention kernel is one program on one chip; sharing a
            # layer or a batch across chips is not written (ROADMAP.md)
            raise NotImplementedError(
                "LoopLMTrainer runs on one chip: no sharding rule exists "
                "for its attention kernel")
        self._train_step = self._jit_train_step()

    def _init_state_impl(self, batch_size: int, seed) -> TrainState:
        del batch_size   # no parameter's shape depends on the batch
        init_key, state_key = jax.random.split(jax.random.PRNGKey(seed))
        return new_train_state(looplm.init_params(init_key, self.cfg), {},
                               self.tx, state_key)

    def loss_fn(self, params, batch):
        """(loss, step metrics) of one batch: what the step differentiates."""
        lm = params["lm"]

        def per_pass(h, gate):
            return lm_loss.chunked_cross_entropy(
                h, lm["head"], batch["labels"], self.dtype), gate

        ce, gates = looplm.run_loop(lm, batch["tokens"], self.cfg, self.dtype,
                                    per_pass)
        return lm_loss.looplm_loss(ce, gates, batch["mask"])

    def _lm_train_step_impl(self, state: TrainState,
                            batch) -> Tuple[TrainState, Dict]:
        (_, metrics), grads = jax.value_and_grad(
            self.loss_fn, has_aux=True)(state.params, batch)
        return self._apply_update(state, grads, metrics, state.batch_stats)

    # ---------------- what the loop logs ----------------

    def log_summary(self, m) -> str:
        t = range(1, self.cfg.total_ut_steps + 1)
        return ("        ce by pass = %s exit q = %s exit entropy = %.4f "
                "tokens = %d\n" % (
                    ["%.4f" % m["ce_ut.%d" % i] for i in t],
                    ["%.3f" % m["exit_q_mean.%d" % i] for i in t],
                    m["exit_entropy"], m["tokens"]))

    def log_gauges(self, m, times) -> Dict[str, float]:
        out = {"train.lm.exit_q_mean.%d" % i: m["exit_q_mean.%d" % i]
               for i in range(1, self.cfg.total_ut_steps + 1)}
        out["train.lm.tokens_per_s"] = m["tokens"] / (times["step_ms"] / 1e3)
        return out
