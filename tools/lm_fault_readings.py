#!/usr/bin/env python
"""What the looped model's reference check reads when the program is made
wrong on purpose: the second reading each limit of
benchmark/drivers/lm_train_loop.py `TOLERANCES` is set against (PERF.md
section 6, PR 31). On the chip, at the cell's timed sizes:

  chiprun -- python tools/lm_fault_readings.py <seed> [variant-name-part]

The seeded weights and the first batch of `ouro_train_packed4k`, the
reference's numbers once, then for the program as shipped and for one fault
at a time what the driver's `observe_first_step` takes round ONE call of the
trainer's compiled step, held against the reference by the driver's
`compare` under the committed limits. Each fault is patched in from here
(the program has no knob for any) and the step and the forward are traced
again under it:
  state_unchanged              the step hands back the state it was given
  half_batch                   the step sees the first row only (the second
                               row's slots masked out; the shipped program)
  attention_bf16_results       every MXU result of the attention kernels
                               rounded to bfloat16 (Mosaic refuses a
                               bfloat16 accumulator outright)
  head_bf16_results            the head's logits leave the MXU as bfloat16
  gate_math_bf16               the exit distribution computed in bfloat16
  all_float32_math_in_bf16     the nearest precision below the
                               configuration's: everything it states as
                               float32 inside the model, the kernels, the
                               head and the cross entropy done in bfloat16
                               (parameters and RoPE's tables stay float32;
                               what the step reports, the loss's means over
                               tokens, stays float32, so the reading is of
                               the precision inside and not of a rounded
                               report)
  pass_dropped                 three passes where the reference runs four
Writes chiprun_out/lm_faults_<seed>.json. `TINY=1` rehearses on the CPU.
"""
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import harness  # noqa: E402


def main():
    seed = int(sys.argv[1])
    only = sys.argv[2] if len(sys.argv) > 2 else ""
    os.makedirs(harness.COMPILE_CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.COMPILE_CACHE_DIR
    cell = harness.Cell("ouro_train_packed4k")
    drv = cell.driver()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from mine_tpu.kernels import attention
    from mine_tpu.models import looplm
    from mine_tpu.train import lm_loss

    if os.environ.get("TINY"):
        cell.workload["config_overrides"] = {
            "lm.hidden_size": 64, "lm.num_attention_heads": 4,
            "lm.num_key_value_heads": 4, "lm.head_dim": 16,
            "lm.intermediate_size": 160, "lm.vocab_size": 512,
            "lm.num_hidden_layers": 2, "data.seq_len": 64}
        cell.workload["dataset"].update(
            {"num_rows": 8, "doc_len_median": 20, "doc_len_min": 4})
    config, dataset, trainer, state = drv.build_program(cell, seed)
    rows = trainer.global_batch_size()
    weights_seed = harness.mix_seed(seed, "weights")
    batch = next(dataset.batch_iterator(
        rows, shuffle=True, seed=harness.mix_seed(seed, "order"), epoch=1))
    ids = drv._frequent_ids(batch)
    # the reference has the chip to itself: the parameters alone stay
    lm = state.params["lm"]
    del state
    t0 = time.time()
    want = drv.reference_numbers(lm, batch, config)
    head = jax.device_get(lm["head"])
    del lm
    print("reference in %.1fs" % (time.time() - t0), flush=True)
    out, kept = {}, {}

    def hold(name, observed, metrics):
        full = dict(want, **drv.same_operand_numbers(
            head, observed["forward"], config))
        out[name] = drv.compare(drv.program_numbers(observed, metrics), full)
        print("%s: %s" % (name, json.dumps(out[name])), flush=True)

    def read(name, patch=None, batch=batch):
        if only and only not in name and name != "as_shipped":
            return
        t0 = time.time()
        state = trainer.init_state(rows, seed=weights_seed)
        if patch is not None:
            patch()
        try:
            # a new function object: the step is traced again, under the patch
            step = jax.jit(lambda s, b: trainer._lm_train_step_impl(s, b),
                           donate_argnums=(0,))
            state, metrics, observed = drv.observe_first_step(
                trainer, state, trainer.put_batch(batch), ids, step)
        finally:
            shipped()
        metrics = jax.device_get(metrics)
        del state
        kept[name] = (observed, metrics)
        print("%s took %.1fs" % (name, time.time() - t0), flush=True)
        hold(name, observed, metrics)

    def rounded_dot(a, b, contract):
        return lax.dot_general(
            a, b, ((contract[0], contract[1]), ((), ())),
            preferred_element_type=jnp.float32).astype(
                jnp.bfloat16).astype(jnp.float32)

    def rounded_head(result_dtype):
        # the float32 result rounded in a step of its own: XLA folds a
        # bfloat16 `preferred_element_type` and a cast back to float32 into
        # the float32 matmul, and nothing is rounded (PERF.md, PR 31)
        def head_logits(hx, head, dtype):
            return jnp.dot(hx, head.astype(dtype),
                           preferred_element_type=jnp.float32
                           ).astype(jnp.bfloat16).astype(result_dtype)
        return head_logits

    class Low:           # a module's `jnp` whose float32 is bfloat16
        float32 = jnp.bfloat16

        def __getattr__(self, name):
            return getattr(jnp, name)

    def bf16_distribution(gates):
        lam = jax.nn.sigmoid(gates.astype(jnp.bfloat16))
        stay = jnp.cumprod(1 - lam[:-1], axis=0)
        before = jnp.concatenate([jnp.ones_like(lam[:1]), stay], axis=0)
        return jnp.concatenate([lam[:-1] * before[:-1], before[-1:]],
                               axis=0).astype(jnp.float32)

    dot, logits, dist, tables, cfg = (
        attention._dot, lm_loss.head_logits, lm_loss.exit_distribution,
        looplm.rope_tables, trainer.cfg)

    def shipped():
        attention._dot, lm_loss.head_logits = dot, logits
        lm_loss.exit_distribution = dist
        looplm.jnp, looplm.rope_tables, trainer.cfg = jnp, tables, cfg

    def float32_tables(*args):
        low, looplm.jnp = looplm.jnp, jnp
        try:
            return tables(*args)
        finally:
            looplm.jnp = low

    def all_low():
        looplm.jnp, looplm.rope_tables = Low(), float32_tables
        attention._dot = rounded_dot
        lm_loss.exit_distribution = bf16_distribution
        lm_loss.head_logits = rounded_head(jnp.bfloat16)  # so is the CE

    read("as_shipped")
    observed, metrics = kept["as_shipped"]
    hold("state_unchanged", dict(observed, after=observed["before"]), metrics)
    first_row = np.array(batch["mask"])
    first_row[1:] = 0
    read("half_batch", batch=dict(batch, mask=first_row))
    read("attention_bf16_results",
         lambda: setattr(attention, "_dot", rounded_dot))
    read("head_bf16_results", lambda: setattr(
        lm_loss, "head_logits", rounded_head(jnp.float32)))
    read("gate_math_bf16", lambda: setattr(
        lm_loss, "exit_distribution", bf16_distribution))
    read("all_float32_math_in_bf16", all_low)
    read("pass_dropped", lambda: setattr(
        trainer, "cfg", dataclasses.replace(cfg, total_ut_steps=3)))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "lm_faults_%d.json" % seed), "w") as f:
        json.dump({"limits": drv.TOLERANCES, "readings": out}, f, indent=1)


if __name__ == "__main__":
    main()
