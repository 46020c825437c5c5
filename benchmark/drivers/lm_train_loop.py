"""Traffic kind `lm_train_loop`: the looped language model's train step fed
by the real loop, and checked against the plain reference.

The program's own objects, wired as train_cli.py and TrainLoop wire them:
`make_trainer` (model.family looplm -> `LoopLMTrainer`), the packed-token
dataset's `batch_iterator(workers=...)` -> `DeviceStager` -> the donated
jitted step, epochs chained without a pause, a device sync at the loop's log
cadence and nowhere else. The feed is `train_loop.py`'s `Feed`, taken from
that file, not copied.

One item of `train_images_per_s` is one packed ROW of `seq_len` tokens (the
benchmark has one train metric; tokens/s is printed in `details`).

The reference check. What is compared comes from the timed path: the first
step the driver runs (the compiled `_lm_train_step_impl`, on the first batch
of the window's own feed and the seeded weights) gives its own loss, four CE
terms, mean exit probabilities and gradient norm, and the change it made to
five named groups of parameters (copied before the call, read after it); a
forward over the same batch (`looplm.run_loop` and the head, under jit at the
timed shapes) gives what those means are taken over: the last pass's cross
entropy and every pass's exit probability of each token, and one token's row
of logits. The other side is benchmark/reference_lm.py (float32, `highest`),
one layer application at a time, its gradients summed over every parameter
for the global norm and pushed through its own clipped AdamW. It runs AFTER
the window on weights made again from the seed, so its seconds are no part
of `setup_s`, and it has the chip's memory to itself.

Everything a later cell may vary is data in its traffic file:
  dataset        {"num_rows", "doc_len_median", "doc_len_sigma",
                 "doc_len_min", "zipf_exponent"} of the packed corpus
  config_overrides  any key of the repo's config space (data.seq_len,
                 data.per_gpu_batch_size = rows a step, lm.*, training.*)
  warmup         steps run before and after one change of epoch
  trace_seconds  length of the profiler window in a traced run
"""

from __future__ import annotations

import os
import statistics
import time

from benchmark import harness

ATTENTION_KERNEL = "flash_attention"   # in the name of its custom calls
FREQUENT_IDS = 16
ROW_AT = (0, -1)   # the token whose row of logits is compared: row 0's last
# the leaf each part of a named group of parameters comes from (`named_parts`)
PART_LEAVES = {"exit_gate": ("w", "b"), "final_norm": ("final_norm",),
               "wq_first": ("wq",), "wd_last": ("wd",),
               "embed_rows": ("embed",)}

# ---- tolerances of the reference check, each with its reason --------------
# Relative error of a scalar, relative L2 error of a vector. The program
# computes with bfloat16 operands, float32 accumulation and a bfloat16
# residual stream (`training.dtype: bfloat16`); the reference is float32
# throughout. Each limit lies between two readings taken on the chip at the
# timed sizes through `compare` (PERF.md section 6, PR 31; the faults are
# tools/lm_fault_readings.py's): the largest the program read over its
# seeds, with about three times of room above it since fresh seeds read
# higher, and what a fault reads.
TOLERANCES = {
    # the first step's own metrics: means over 8,192 tokens, and the norm of
    # every parameter's gradient
    "step.loss": 5e-4,          # program <= 1.5e-4; half the batch 2.2e-3
    "step.ce_ut": 6e-4,         # program <= 1.6e-4; half the batch 2.9e-3;
                                # a pass dropped: three terms, not four
    "step.exit_q_mean": 2e-2,   # program <= 5.8e-3; half the batch 0.14
    "step.grad_norm": 6e-3,     # program <= 1.9e-3; a pass dropped 0.0093,
                                # 0.026; half the batch 0.051, 0.19
    # the change the first step made to the parameters against the
    # reference's clipped AdamW, on the half of each group's elements whose
    # reference gradient is the larger: Adam's first step is lr * sign(g),
    # and an element whose gradient is inside the operands' noise has no
    # sign to agree on. The program reads 1e-6 to 6e-5, and sqrt(4 k / n)
    # when k of the n compared elements do flip (0.0156: one of 16,384
    # embedding elements). A state left unchanged reads exactly 1, half the
    # batch 0.26 to 0.77, an update at another rate |1 - ratio|. The limits
    # sit nearer the program than 1, with room for some tens of flips (ten
    # in the two groups of a thousand elements).
    "delta.exit_gate": 0.2,
    "delta.final_norm": 0.2,
    "delta.wq_first": 0.1,
    "delta.wd_last": 0.1,
    "delta.embed_rows": 0.1,
    # per token, nothing averaged: the operands' roundings through 32 layer
    # applications show whole, and a lower precision inside hardly adds to
    # them (everything float32 done in bfloat16 reads 2.8e-3, 6.0e-3, 0.026)
    "token.ce_last": 7.5e-3,    # program <= 2.5e-3; a pass dropped 0.069
    "token.exit_q": 3e-2,       # program <= 9.3e-3; a pass dropped: 3 of 4
    "token.logits_row": 9e-2,   # program <= 0.031; a pass dropped 0.76
    # one block alone, the reference's float32 on the program's own input
    # rounded as the configuration states the operands: what is left is the
    # accumulation and the float32 math, so a result rounded to bfloat16
    # shows whole. This is where a precision below the stated one fails.
    "same.head": 1e-4,          # program 0 (bit for bit); the head's result
                                # in bfloat16 1.64e-3
    "same.exit_q": 1e-4,        # program 0; the gate's math in bfloat16 1.9e-3
}


def _load_sibling(name):
    return harness.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), name + ".py"),
        "driver_" + name)


def build_program(cell, seed):
    """(config, dataset, trainer, seeded state) of the cell, everything from
    `seed`: what `setup` times and tools/lm_fault_readings.py re-reads."""
    import jax

    try:
        config = cell.program_config()
        from mine_tpu.data.tokens import PackedTokenDataset
        from mine_tpu.train.trainer import make_trainer
    except (OSError, KeyError, ImportError) as e:
        # a program from before this family: no YAML, no lm.* keys, no module
        raise harness.BenchError("this program cannot run %s: %r"
                                 % (cell.name, e))
    if cell.chips != 1:
        raise harness.BenchError("the looped model's step runs on one chip")
    rows = int(config["data.per_gpu_batch_size"])
    t0 = time.perf_counter()
    ds_cfg = dict(cell.workload["dataset"])
    dataset = PackedTokenDataset(
        num_rows=int(ds_cfg.pop("num_rows")),
        seq_len=int(config["data.seq_len"]),
        vocab_size=int(config["lm.vocab_size"]),
        seed=harness.mix_seed(seed, "corpus"), **ds_cfg)
    harness.say("corpus: %d rows of %d tokens, %d documents, fill %.4f, in "
                "%.1fs" % (len(dataset), dataset.seq_len,
                           len(dataset.doc_lengths),
                           float(dataset.valid.mean()),
                           time.perf_counter() - t0))
    trainer = make_trainer(config, mesh=None,
                           steps_per_epoch=max(1, len(dataset) // rows))
    t0 = time.perf_counter()
    state = trainer.init_state(rows, seed=harness.mix_seed(seed, "weights"))
    jax.block_until_ready(state.step)
    harness.say("init_state in %.1fs" % (time.perf_counter() - t0))
    return config, dataset, trainer, state


def setup(cell, seed, devices, spans):
    import jax

    config, dataset, trainer, state = build_program(cell, seed)
    wl = cell.workload
    rows, seq_len = int(config["data.per_gpu_batch_size"]), dataset.seq_len
    steps_per_epoch = trainer.steps_per_epoch
    ctx = {"cell": cell, "config": config, "trainer": trainer,
           "devices": list(devices[:1]), "spans": spans, "state": state,
           "rows": rows, "seq_len": seq_len, "seed": seed,
           "steps_per_epoch": steps_per_epoch,
           "log_interval": max(1, min(int(config.get(
               "training.log_interval", 10)), steps_per_epoch)),
           "metrics": [], "temp_bytes": 0}

    warm = wl.get("warmup", {})
    before = min(int(warm.get("steps_before_epoch_end", 2)), steps_per_epoch)
    after = int(warm.get("steps_after_epoch_start", 2))
    ctx["feed"] = _load_sibling("train_loop").Feed(
        dataset, trainer, config, harness.mix_seed(seed, "order"), epoch=1,
        offset=steps_per_epoch - before)
    first = ctx["feed"].next()
    ctx["first_batch"] = jax.device_get(first)

    t0 = time.perf_counter()
    ctx["temp_bytes"], ctx["kernel_calls"] = _step_program_facts(
        trainer, state, first)
    harness.say("step program: %.2f GiB beside its arguments at its peak "
                "by memory_analysis, %d %s "
                "custom calls (%.1fs)" % (
                    ctx["temp_bytes"] / 2**30, ctx["kernel_calls"],
                    ATTENTION_KERNEL, time.perf_counter() - t0))
    t0 = time.perf_counter()
    ctx["state"], metrics, ctx["observed"] = observe_first_step(
        trainer, state, first, _frequent_ids(ctx["first_batch"]),
        trainer.train_step)
    ctx["metrics"].append(_kept(metrics))
    harness.say("first step (compile or cache load + run), the forward over "
                "its batch and the named parameters on both sides of it in "
                "%.1fs" % (time.perf_counter() - t0))
    for _ in range(before - 1 + after):
        _step(ctx, ctx["feed"].next())
    jax.block_until_ready((ctx["state"].step, ctx["metrics"][-1]))
    return ctx


# ---------------- the reference check ----------------

def _ref_config(config):
    return {k: config["lm." + k] for k in (
        "hidden_size", "num_attention_heads", "head_dim",
        "num_hidden_layers", "total_ut_steps", "rms_norm_eps", "rope_theta")}


def _flat(x):
    import numpy as np
    return np.concatenate([np.ravel(np.asarray(a, np.float64))
                           for a in (x if isinstance(x, list) else [x])])


def _frequent_ids(batch):
    import numpy as np
    return np.argsort(-np.bincount(np.asarray(batch["tokens"]).ravel()),
                      kind="stable")[:FREQUENT_IDS]


def named_parts(lm, ids):
    """The five named groups of a tree shaped like the parameters (the
    parameters, or their gradients), each a list of flat parts; one jitted
    program, whose outputs are copies."""
    import jax

    def take(lm, ids):
        return {"exit_gate": [lm["exit_gate"]["w"],
                              lm["exit_gate"]["b"].reshape(1)],
                "final_norm": [lm["final_norm"]],
                "wq_first": [lm["layers"]["wq"][0].ravel()],
                "wd_last": [lm["layers"]["wd"][-1].ravel()],
                "embed_rows": [lm["embed"][ids].ravel()]}
    return jax.jit(take)(lm, ids)


def forward_numbers(trainer, params, batch):
    """What the step's means are taken over, from the program's forward
    called once under jit at the timed shapes: the last pass's cross entropy
    and every pass's gate and exit probability of each token, and for the
    token `ROW_AT` the last pass's hidden state and its row of logits."""
    import jax
    import jax.numpy as jnp

    from mine_tpu.models import looplm
    from mine_tpu.train import lm_loss
    row, pos = ROW_AT

    def forward(params, batch):
        lm = params["lm"]

        def per_pass(h, gate):
            ce = lm_loss.chunked_cross_entropy(h, lm["head"], batch["labels"],
                                               trainer.dtype)
            return ce, gate, h[row, pos]

        ce, gates, h_rows = looplm.run_loop(lm, batch["tokens"], trainer.cfg,
                                            trainer.dtype, per_pass)
        return {"ce_last": ce[-1], "gates": gates,
                "exit_q": lm_loss.exit_distribution(gates),
                "h_row": h_rows[-1].astype(jnp.float32),
                "logits_row": lm_loss.head_logits(h_rows[-1], lm["head"],
                                                  trainer.dtype)}
    return jax.device_get(jax.jit(forward)(params, batch))


def observe_first_step(trainer, state, batch, ids, step):
    """The program's side of the check, taken round ONE call of `step` (the
    trainer's compiled step, which donates `state`): the forward's per-token
    numbers over `batch`, and the named parameters before and after.
    -> (the new state, the step's metrics, what was observed, on the host)"""
    import jax
    observed = {"forward": forward_numbers(trainer, state.params, batch),
                "before": named_parts(state.params["lm"], ids)}
    state, metrics = step(state, batch)
    observed["after"] = named_parts(state.params["lm"], ids)
    return state, metrics, jax.device_get(observed)


def program_numbers(observed, first_metrics):
    """The program's side of `compare`, from what `setup` observed round
    the first step and that step's own metrics."""
    import numpy as np
    fwd = observed["forward"]
    got = {"step.loss": first_metrics["loss"],
           "step.ce_ut": first_metrics["ce_ut"],
           "step.exit_q_mean": first_metrics["exit_q_mean"],
           "step.grad_norm": first_metrics["grad_norm"],
           "token.ce_last": fwd["ce_last"], "token.exit_q": fwd["exit_q"],
           "token.logits_row": fwd["logits_row"],
           "same.head": fwd["logits_row"], "same.exit_q": fwd["exit_q"]}
    for k, after in observed["after"].items():
        # in float64: a change of 3e-4 of a parameter is 12 bits of float32
        got["delta." + k] = [np.asarray(a, np.float64) - np.asarray(
            b, np.float64) for a, b in zip(after, observed["before"][k])]
    return got


def reference_numbers(lm, batch, config):
    """The reference's side, from the seeded weights `lm` and the host
    batch: the loss and its terms, every parameter's gradient (for the
    global norm) pushed through its own clipped AdamW on the named groups,
    and the per-token numbers. `where` marks what of a vector is compared."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_lm as R
    loss, terms, per_token, grads = R.blockwise_loss_and_grads(
        lm, batch, _ref_config(config), ROW_AT)
    lr, wd = float(config["lr.lm_lr"]), float(config["lr.weight_decay"])
    ids = _frequent_ids(batch)

    def first_step(lm, grads, gates):
        norm = R.global_norm(grads)
        p, g = named_parts(lm, ids), named_parts(grads, ids)
        delta = {k: [R.adamw_step(pp, R.clip_scale(norm) * gg, 0.0, 0.0, 1,
                                  lr, wd, R.decayed(leaf))[0] - pp
                     for pp, gg, leaf in zip(p[k], g[k], PART_LEAVES[k])]
                 for k in p}
        return norm, delta, g, jnp.stack(R.exit_distribution(list(gates)))

    norm, delta, g, q = jax.device_get(jax.jit(first_step)(
        lm, grads, per_token["gates"]))
    valid = np.asarray(batch["mask"]) > 0
    want = {"step.loss": loss, "step.ce_ut": terms["ce_ut"],
            "step.exit_q_mean": terms["exit_q_mean"], "step.grad_norm": norm,
            "token.ce_last": per_token["ce"][-1], "token.exit_q": q,
            "token.logits_row": per_token["logits_row"],
            "where": {"token.ce_last": valid.ravel(),
                      "token.exit_q": np.broadcast_to(valid, q.shape).ravel()}}
    for k in delta:
        want["delta." + k] = delta[k]
        size = np.abs(_flat(g[k]))
        want["where"]["delta." + k] = size >= np.median(size)
    return jax.device_get(want)


def same_operand_numbers(head, forward, config):
    """One block alone, the reference's float32 on the program's own input:
    the head's row of logits from the program's hidden state and the weights
    rounded as the configuration states the MXU's operands, and the exit
    distribution from the program's gates."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_lm as R
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config.get("training.dtype", "bfloat16")]

    def blocks(head, h_row, gates):
        with jax.default_matmul_precision("highest"):
            return {"same.head": h_row @ head.astype(dtype).astype(
                jnp.float32),
                    "same.exit_q": jnp.stack(R.exit_distribution(list(
                        gates.astype(jnp.float32))))}
    return jax.device_get(jax.jit(blocks)(head, forward["h_row"],
                                          forward["gates"]))


def compare(got, want):
    """{"errors": {name: relative error}, "ok": all within TOLERANCES}."""
    where = want.get("where", {})

    def rel(k):
        import numpy as np
        g, w = _flat(got[k]), _flat(want[k])
        if g.shape != w.shape:        # a pass too few: nothing to hold it to
            return float("inf")
        if k in where:
            g, w = g[where[k]], w[where[k]]
        return float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))

    errors = {k: rel(k) for k in TOLERANCES}
    over = [k for k in TOLERANCES if not errors[k] <= TOLERANCES[k]]
    return {"errors": {k: float("%.3g" % v) for k, v in errors.items()},
            "ok": not over, "over": over,
            "loss": float(got["step.loss"]),
            "reference_loss": float(want["step.loss"])}


def reference_check(ctx, first_metrics):
    """After the window: the trained state leaves the chip, the weights are
    made again from the seed, the reference runs on them and the first
    batch, and what `setup` observed of the program is held against it."""
    trainer, config = ctx["trainer"], ctx["config"]
    seed = harness.mix_seed(ctx["seed"], "weights")
    ctx["state"] = None
    lm = trainer.init_state(ctx["rows"], seed=seed).params["lm"]
    want = reference_numbers(lm, ctx["first_batch"], config)
    want.update(same_operand_numbers(lm["head"], ctx["observed"]["forward"],
                                     config))
    del lm
    # run.py reads the device's memory after this returns: what the window
    # held is resident again by then
    ctx["state"] = trainer.init_state(ctx["rows"], seed=seed)
    return compare(program_numbers(ctx["observed"], first_metrics), want)


def _step_program_facts(trainer, state, batch):
    """(what the step program needs beside its arguments at its peak, by the
    compiler's memory analysis; how many attention-kernel custom calls the
    compiled step holds). The compile is the one the first step makes.
    `peak_memory_in_bytes` less the arguments, where the analysis has it:
    `temp_size_in_bytes` adds up buffers that are never live together (it
    reads 10.54 GiB here beside 6.84 GiB of state, on a chip of 15.75)."""
    try:
        compiled = trainer._train_step.lower(state, batch).compile()
        analysis = compiled.memory_analysis()
        peak = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)
        temp = (peak - int(analysis.argument_size_in_bytes) if peak
                else int(getattr(analysis, "temp_size_in_bytes", 0) or 0))
        return max(temp, 0), compiled.as_text().count(ATTENTION_KERNEL)
    except Exception as e:  # noqa: BLE001 - a missing analysis is not a fault
        harness.say("no analysis of the step program: %r" % (e,))
        return 0, 0


def _kept(metrics):
    return {k: metrics[k] for k in (
        "loss", "skipped_steps", "ce_ut", "exit_q_mean", "exit_entropy",
        "tokens", "grad_norm") if k in metrics}


def _step(ctx, batch):
    ctx["state"], metrics = ctx["trainer"].train_step(ctx["state"], batch)
    # stays on the device: fetched after the window closes
    ctx["metrics"].append(_kept(metrics))


def _pack_counters():
    from mine_tpu import telemetry
    return (telemetry.counter("data.pack.tokens").value,
            telemetry.counter("data.pack.slots").value)


def measure(ctx, seconds, tracer, watch):
    import jax
    spans, feed = ctx["spans"], ctx["feed"]
    log_interval = ctx["log_interval"]
    first_index = len(ctx["metrics"])
    pack0 = _pack_counters()
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
    pack1 = _pack_counters()
    if tracer is not None:
        tracer.join()

    # ---- after the window: read back, check ----
    fetched = jax.device_get(ctx["metrics"])
    t0 = time.perf_counter()
    ctx["reference"] = reference_check(ctx, fetched[0])
    harness.say("reference check in %.1fs, after the window: %s" % (
        time.perf_counter() - t0, ctx["reference"]))
    losses = [float(m["loss"]) for m in fetched]
    skipped = int(fetched[-1].get("skipped_steps", 0))
    in_window = watch.between(wall0, wall1)
    tail = statistics.median(losses[-5:])
    on_chip = harness.REQUIRED_PLATFORM == "tpu"
    checks = {
        "reference": bool(ctx["reference"]["ok"]),
        "losses_finite": all(x == x and abs(x) != float("inf")
                             for x in losses),
        "no_skipped_steps": skipped == 0,
        "loss_fell": tail < losses[0],
        "no_compile_in_window": not in_window,
        # on the chip the Pallas attention kernel is the path under test;
        # the CPU rehearsal of the tests runs the same function in XLA
        "attention_kernel_in_step": ctx["kernel_calls"] >= 3 or not on_chip,
    }
    last = fetched[-1]
    harness.say("losses: warm-up %s; window first %s last %s; median of "
                "last five %.4f" % (
                    [round(x, 4) for x in losses[:first_index]],
                    [round(x, 4) for x in losses[first_index:first_index + 3]],
                    [round(x, 4) for x in losses[-3:]], tail))
    harness.say("last step: ce by pass %s, mean exit q %s, exit entropy "
                "%.4f; skipped_steps %d; compile requests in window: %s" % (
                    [round(float(x), 4) for x in last["ce_ut"]],
                    [round(float(x), 4) for x in last["exit_q_mean"]],
                    float(last["exit_entropy"]), skipped, in_window))
    harness.say("checks: %s" % checks)
    program = ctx["trainer"].STEP_IMPL
    step_cache = watch.summary(program)
    harness.say("train step program %s: persistent-cache hits %d, misses %d"
                % (program, step_cache["hits"], step_cache["misses"]))
    rows = steps * ctx["rows"]
    tokens = rows * ctx["seq_len"]
    return {
        "window_start": wall0, "window_s": window_s,
        "attempted": steps, "failed": skipped,
        "correct": all(checks.values()), "checks": checks,
        # one item is one packed row of seq_len tokens
        "end_to_end": {"train_images_per_s": rows / window_s},
        "counters": {"steps": steps, "rows": rows,
                     "rows_per_step": ctx["rows"],
                     "steps_per_epoch": ctx["steps_per_epoch"],
                     "pack_tokens": pack1[0] - pack0[0],
                     "pack_slots": pack1[1] - pack0[1],
                     "step_program": program},
        "shapes": _shapes(ctx),
        "temp_bytes": ctx["temp_bytes"],
        "details": {
            "tokens_per_s": "%.1f (%d tokens a row)" % (tokens / window_s,
                                                        ctx["seq_len"]),
            "reference_errors": ctx["reference"]["errors"]},
    }


def _shapes(ctx):
    """What benchmark/roofline_lm.py prices one step from."""
    cfg = ctx["trainer"].cfg
    return {"kind": "lm_train", "rows_per_step": ctx["rows"],
            "seq_len": ctx["seq_len"], "hidden": cfg.hidden_size,
            "heads": cfg.num_attention_heads, "head_dim": cfg.head_dim,
            "intermediate": cfg.intermediate_size,
            "layers": cfg.num_hidden_layers, "passes": cfg.total_ut_steps,
            "vocab": cfg.vocab_size}


def teardown(ctx):
    """Stop the feed's threads and wait for them (train_loop.py: a stager
    thread still inside a device copy at interpreter shutdown aborts)."""
    ctx["feed"].close()
