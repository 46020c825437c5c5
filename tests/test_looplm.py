"""The looped language model (model.family: looplm) on the CPU at a small
size, seeded random weights: the trainer against the plain reference
(benchmark/reference_lm.py), the loop's identities, the attention kernel in
interpret mode, the chunked loss, the packer, checkpoints, the optimizer's
groups, and the lazy imports."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mine_tpu.config import CONFIG_DIR, load_config  # noqa: E402

TINY = {"lm.hidden_size": 64, "lm.num_attention_heads": 4,
        "lm.num_key_value_heads": 4, "lm.head_dim": 16,
        "lm.intermediate_size": 160, "lm.vocab_size": 512,
        "lm.num_hidden_layers": 2, "lm.total_ut_steps": 4,
        "data.seq_len": 64,
        "data.per_gpu_batch_size": 2, "training.log_interval": 2}
REF_KEYS = ("hidden_size", "num_attention_heads", "head_dim",
            "num_hidden_layers", "total_ut_steps", "rms_norm_eps",
            "rope_theta")


def tiny_config(**extra):
    return load_config(os.path.join(CONFIG_DIR, "params_ouro_2p6b.yaml"),
                       extra_config=dict(TINY, **extra))


def ref_cfg(config):
    return {k: config["lm." + k] for k in REF_KEYS}


def make_trainer(**extra):
    from mine_tpu.train.lm_step import LoopLMTrainer
    return LoopLMTrainer(tiny_config(**extra), steps_per_epoch=4)


def make_batch(config, seed=0, rows=2):
    from mine_tpu.data.tokens import dataset_from_config
    ds = dataset_from_config(config, seed=seed)
    batch = next(ds.batch_iterator(rows, shuffle=True, seed=seed, epoch=1))
    return {k: jnp.asarray(v) for k, v in batch.items()}


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(scope="module")
def f32():
    """Trainer, seeded state, batch and the reference's numbers, float32."""
    from benchmark import reference_lm
    trainer = make_trainer(**{"training.dtype": "float32"})
    state = trainer.init_state(2, seed=3)
    batch = make_batch(trainer.config)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        trainer.loss_fn, has_aux=True))(state.params, batch)
    want = reference_lm.loss_and_grads(state.params["lm"], batch,
                                       ref_cfg(trainer.config))
    return dict(trainer=trainer, state=state, batch=batch, loss=loss,
                metrics=metrics, grads=grads["lm"], want=want)


# float32 against float32: what differs is the order of sums (scans, the
# blocked softmax's rescaling, chunked sums), a few float32 roundings deep
TIGHT = 2e-5


@pytest.mark.parametrize("term", ["loss", "ce_ut", "exit_q_mean",
                                  "exit_entropy", "tokens"])
def test_float32_terms_match_reference(f32, term):
    want_loss, want_terms, _ = f32["want"]
    want = want_loss if term == "loss" else want_terms[term]
    assert rel(f32["metrics"][term], want) < TIGHT


def _leaves(tree):
    return {jax.tree_util.keystr(p): v for p, v in
            jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("leaf", [
    "['embed']", "['head']", "['final_norm']", "['exit_gate']['w']",
    "['exit_gate']['b']"] + ["['layers']['%s']" % n for n in (
        "wq", "wk", "wv", "wo", "wg", "wu", "wd", "norm1", "norm2", "norm3",
        "norm4")])
def test_float32_gradient_matches_reference(f32, leaf):
    got, want = _leaves(f32["grads"])[leaf], _leaves(f32["want"][2])[leaf]
    assert np.linalg.norm(np.asarray(want)) > 0
    assert rel(got, want) < 1e-4   # a gradient is a longer chain of sums


# bfloat16 operands with float32 accumulation and a bfloat16 residual stream
# against the float32 reference: each of the 8 layer applications rounds its
# output to 8 bits of mantissa (2^-9 relative), and the loss is a mean over
# tokens, so the means stay within a percent while single gradients, which
# are not averaged, carry the roundings of the whole chain
def test_bfloat16_step_within_stated_tolerance(f32):
    trainer = make_trainer()
    assert trainer.dtype == jnp.bfloat16
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        trainer.loss_fn, has_aux=True))(f32["state"].params, f32["batch"])
    want_loss, want_terms, want_grads = f32["want"]
    assert rel(loss, want_loss) < 1e-2
    assert rel(metrics["ce_ut"], want_terms["ce_ut"]) < 1e-2
    assert rel(metrics["exit_q_mean"], want_terms["exit_q_mean"]) < 1e-2
    got, want = _leaves(grads["lm"]), _leaves(want_grads)
    for leaf in want:
        assert rel(got[leaf], want[leaf]) < 0.15, leaf


def test_tied_gradient_is_sum_of_per_pass_gradients(f32):
    """The gradient of a weight the four passes share equals the sum of the
    four gradients of an untied copy, one set of layer weights a pass."""
    from benchmark import reference_lm as R
    lm, batch, cfg = f32["state"].params["lm"], f32["batch"], ref_cfg(
        f32["trainer"].config)
    T = cfg["total_ut_steps"]

    def untied_loss(stacks):   # stacks: [T] + the layer tree's shapes
        sums = []
        for b in range(batch["tokens"].shape[0]):
            with jax.default_matmul_precision("highest"):
                x = lm["embed"][batch["tokens"][b]]
                ce, gates = [], []
                for t in range(T):
                    for l in range(cfg["num_hidden_layers"]):
                        x = R.layer_apply(x, jax.tree_util.tree_map(
                            lambda a: a[t, l], stacks), cfg)
                    x, g = R.pass_end(x, lm["final_norm"], lm["exit_gate"],
                                      cfg)
                    ce.append(R.cross_entropy(x, lm["head"],
                                              batch["labels"][b]))
                    gates.append(g)
                sums.append(R.token_sums(ce, gates, batch["mask"][b]))
        return R._means(sums)[0]

    stacks = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (T,) + a.shape), lm["layers"])
    per_pass = jax.grad(untied_loss)(stacks)
    for name, g in f32["grads"]["layers"].items():
        assert rel(g, jnp.sum(per_pass[name], axis=0)) < 1e-4, name


def test_exit_distribution_sums_to_one_and_one_pass_is_plain_ce():
    from mine_tpu.train import lm_loss
    gates = jax.random.normal(jax.random.PRNGKey(0), (4, 3, 7)) * 3.0
    q = lm_loss.exit_distribution(gates)
    np.testing.assert_allclose(np.asarray(q.sum(0)), 1.0, atol=1e-6)
    assert float(q.min()) >= 0.0
    ce = jax.random.uniform(jax.random.PRNGKey(1), (1, 3, 7)) + 1.0
    mask = jnp.ones((3, 7))
    loss, m = lm_loss.looplm_loss(ce, gates[:1], mask)
    np.testing.assert_allclose(float(loss), float(ce.mean()), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(m["exit_q_mean"]), [1.0])
    assert float(m["exit_entropy"]) == 0.0


def test_scan_over_passes_equals_unrolled_loop_bitwise(f32):
    from mine_tpu.models import looplm
    from mine_tpu.train import lm_loss
    trainer, params, batch = f32["trainer"], f32["state"].params, f32["batch"]

    def unrolled_loss(p, b):   # the passes as a Python loop over `make_pass`
        lm = p["lm"]
        one_pass = looplm.make_pass(
            lm, b["tokens"].shape[1], trainer.cfg, trainer.dtype,
            lambda h, gate: (lm_loss.chunked_cross_entropy(
                h, lm["head"], b["labels"], trainer.dtype), gate))
        x, outs = looplm.embed(lm, b["tokens"], trainer.dtype), []
        for _ in range(trainer.cfg.total_ut_steps):
            x, out = one_pass(x, None)
            outs.append(out)
        ce, gates = (jnp.stack(xs) for xs in zip(*outs))
        return lm_loss.looplm_loss(ce, gates, b["mask"])

    rolled = jax.jit(trainer.loss_fn)(params, batch)
    unrolled = jax.jit(unrolled_loss)(params, batch)
    for a, b in zip(jax.tree_util.tree_leaves(rolled),
                    jax.tree_util.tree_leaves(unrolled)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("seq_len", [64, 128, 384])
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_attention_kernel_matches_plain_attention(seq_len, what):
    from mine_tpu.kernels import attention as A
    B, H, D = 2, 4, 16
    q, k, v, g = (jax.random.normal(key, (B, seq_len, H * D), jnp.float32)
                  for key in jax.random.split(jax.random.PRNGKey(seq_len), 4))
    kernel = lambda q, k, v: A.flash_attention(q, k, v, H,  # noqa: E731
                                               interpret=True)
    plain = lambda q, k, v: A.plain_attention(q, k, v, H)   # noqa: E731
    if what == "forward":
        np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                                   np.asarray(plain(q, k, v)), atol=2e-6)
        return
    grad = lambda f: jax.grad(  # noqa: E731
        lambda q, k, v: jnp.sum(f(q, k, v) * g), (0, 1, 2))(q, k, v)
    for a, b in zip(grad(kernel), grad(plain)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-6)


def test_attention_block_sizes():
    from mine_tpu.kernels.attention import block_size
    assert [block_size(s) for s in (64, 128, 384, 4096)] == [64, 128, 128,
                                                            512]


def test_chunked_head_and_loss_equals_unchunked():
    from mine_tpu.train import lm_loss
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    h = jax.random.normal(keys[0], (2, 64, 32))
    head = jax.random.normal(keys[1], (32, 512)) * 0.1
    labels = jax.random.randint(keys[2], (2, 64), 0, 512)

    def total(chunk):
        return lambda h, head: jnp.sum(lm_loss.chunked_cross_entropy(
            h, head, labels, jnp.float32, chunk=chunk) ** 2)

    for chunk in (16, 32):
        np.testing.assert_allclose(
            np.asarray(lm_loss.chunked_cross_entropy(
                h, head, labels, jnp.float32, chunk=chunk)),
            np.asarray(lm_loss.chunked_cross_entropy(
                h, head, labels, jnp.float32, chunk=128)), rtol=1e-6)
        for a, b in zip(jax.grad(total(chunk), (0, 1))(h, head),
                        jax.grad(total(128), (0, 1))(h, head)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
    assert lm_loss.token_chunk(8192) == 1024 and lm_loss.token_chunk(96) == 96


def test_blockwise_reference_equals_whole_reference(f32):
    """What the chip's check computes one layer application at a time is
    what `loss_and_grads` computes in one piece."""
    from benchmark import reference_lm as R
    lm, cfg = f32["state"].params["lm"], ref_cfg(f32["trainer"].config)
    batch = {k: np.asarray(v) for k, v in f32["batch"].items()}
    loss, terms, per_token, grads = R.blockwise_loss_and_grads(
        lm, batch, cfg, row_at=(1, 7))
    want_loss, want_terms, want = f32["want"]
    assert rel(loss, want_loss) < 1e-6
    for k in want_terms:
        assert rel(terms[k], want_terms[k]) < 1e-6, k
    got, want = _leaves(grads), _leaves(want)
    assert set(got) == set(want)
    for leaf in want:     # every parameter's gradient, so the global norm too
        assert rel(got[leaf], want[leaf]) < 1e-5, leaf
    # what the means are taken over: each token's CE and gate, one row of logits
    mask = batch["mask"]
    assert per_token["ce"].shape == per_token["gates"].shape == (4, 2, 64)
    assert rel((per_token["ce"] * mask).sum((1, 2)) / mask.sum(),
               want_terms["ce_ut"]) < 1e-6
    with jax.default_matmul_precision("highest"):
        x = lm["embed"][batch["tokens"][1]]
        for _ in range(cfg["total_ut_steps"]):
            for l in range(cfg["num_hidden_layers"]):
                x = R.layer_apply(x, jax.tree_util.tree_map(
                    lambda a: a[l], lm["layers"]), cfg)
            x, gate = R.pass_end(x, lm["final_norm"], lm["exit_gate"], cfg)
        assert rel(per_token["logits_row"], x[7] @ lm["head"]) < 1e-6
    assert rel(per_token["gates"][-1, 1], gate) < 1e-6


# ---------------- the packer ----------------

def test_packer_same_seed_same_rows_and_lengths_clipped():
    from mine_tpu.data.tokens import PackedTokenDataset
    mk = lambda seed: PackedTokenDataset(  # noqa: E731
        num_rows=16, seq_len=256, vocab_size=512, seed=seed,
        doc_len_median=60, doc_len_sigma=1.2, doc_len_min=8)
    a, b, c = mk(1), mk(1), mk(2)
    assert np.array_equal(a.rows, b.rows) and not np.array_equal(a.rows,
                                                                 c.rows)
    assert a.doc_lengths[:-1].min() >= 8 and a.doc_lengths.max() <= 256
    assert a.rows.min() >= 0 and a.rows.max() < 512
    # a row's labels are its tokens shifted by one, across the row's end too
    assert np.array_equal(a.rows[0, 1:], a.get_row(0)["labels"])
    assert a.rows[0, -1] == a.rows[1, 0]
    # Zipf: the most frequent id is far more frequent than the median one
    counts = np.bincount(a.rows.ravel(), minlength=512)
    assert counts.max() > 20 * max(np.median(counts), 1)


def test_packer_counts_tokens_and_slots_and_fill():
    from mine_tpu import telemetry
    from mine_tpu.data.tokens import PackedTokenDataset
    ds = PackedTokenDataset(num_rows=16, seq_len=256, vocab_size=512, seed=4,
                            doc_len_median=60, doc_len_min=8)
    t0 = telemetry.counter("data.pack.tokens").value
    s0 = telemetry.counter("data.pack.slots").value
    a = list(ds.batch_iterator(2, shuffle=True, seed=7, epoch=1))
    b = list(ds.batch_iterator(2, shuffle=True, seed=7, epoch=1, workers=2))
    assert len(a) == 8
    for x, y in zip(a, b):   # any worker count, the same batches
        assert all(np.array_equal(x[k], y[k]) for k in x)
    tokens = telemetry.counter("data.pack.tokens").value - t0
    slots = telemetry.counter("data.pack.slots").value - s0
    assert slots == 2 * 16 * 256 and tokens <= slots
    assert tokens / slots >= 0.99   # the fill the traffic file states
    assert tokens == 2 * int(ds.valid.sum())


# ---------------- the trainer through the shared update ----------------

def test_train_step_learns_and_reports_its_metrics():
    from mine_tpu import telemetry
    trainer = make_trainer(**{"training.dtype": "float32", "lr.lm_lr": 3e-3})
    state = trainer.init_state(2, seed=0)
    batch = make_batch(trainer.config)
    losses = []
    for _ in range(6):
        state, m = trainer.train_step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] and int(m["skipped_steps"]) == 0
    assert m["ce_ut"].shape == (4,) and m["exit_q_mean"].shape == (4,)
    assert float(m["tokens"]) == float(batch["mask"].sum())
    assert telemetry.programs.registered("_lm_train_step_impl")
    layers = telemetry.programs.layers("_lm_train_step_impl")
    assert set(layers.values()) == set(
        telemetry.programs.FAMILY_LAYERS["looplm"])
    telemetry.programs.reset()


def test_checkpoint_roundtrip_of_the_new_tree(tmp_path):
    from mine_tpu.train.checkpoint import CheckpointManager
    trainer = make_trainer(**{"training.dtype": "float32"})
    state = trainer.init_state(2, seed=1)
    state, _ = trainer.train_step(state, make_batch(trainer.config))
    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save_latest(state)
    ckpt.wait()
    restored = ckpt.restore(trainer.init_state(2, seed=2))
    assert int(restored.step) == 1
    for a, b in zip(jax.tree_util.tree_leaves((state.params,
                                               state.opt_state)),
                    jax.tree_util.tree_leaves((restored.params,
                                               restored.opt_state))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_mine_groups_get_the_update_they_got_before():
    """`make_optimizer` over the MINE groups is Adam with the L2 decay folded
    in before the moments and one MultiStepLR a group, as written out."""
    import optax
    from mine_tpu.train.state import (lr_groups, make_optimizer,
                                      multistep_lr)
    config = load_config(os.path.join(CONFIG_DIR, "params_llff.yaml"),
                         extra_config={"lr.backbone_lr": 1e-3,
                                       "lr.decoder_lr": 3e-3})
    assert lr_groups(config) == ("backbone", "decoder")
    assert lr_groups(tiny_config()) == ("lm",)
    params = {"backbone": {"w": jnp.arange(6.0).reshape(2, 3)},
              "decoder": {"w": jnp.ones((3,)), "b": jnp.full((2,), -2.0)}}
    grads = jax.tree_util.tree_map(lambda p: 0.1 * p + 0.5, params)

    def before(lr):
        return optax.chain(
            optax.add_decayed_weights(float(config["lr.weight_decay"])),
            optax.scale_by_adam(b1=0.9, b2=0.999, eps=1e-8),
            optax.scale_by_learning_rate(multistep_lr(
                lr, config["lr.decay_steps"], 0.1, 100)))

    old = optax.multi_transform(
        {"backbone": before(1e-3), "decoder": before(3e-3)},
        lambda p: {k: k for k in p})
    new = make_optimizer(config, steps_per_epoch=100)
    s_old, s_new = old.init(params), new.init(params)
    assert (jax.tree_util.tree_structure(s_old)
            == jax.tree_util.tree_structure(s_new))
    for _ in range(3):
        u_old, s_old = old.update(grads, s_old, params)
        u_new, s_new = new.update(grads, s_new, params)
        for a, b in zip(jax.tree_util.tree_leaves(u_old),
                        jax.tree_util.tree_leaves(u_new)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_adamw_decays_matrices_only_and_clips():
    from mine_tpu.train.lm_step import _decays
    trainer = make_trainer(**{"training.dtype": "float32"})
    params = trainer.init_state(2, seed=0).params
    mask = _decays(params)["lm"]
    assert mask["embed"] and mask["head"] and mask["layers"]["wq"]
    assert mask["exit_gate"]["w"] and not mask["exit_gate"]["b"]
    assert not mask["final_norm"] and not mask["layers"]["norm3"]
    # a zero gradient moves a decayed leaf and leaves a norm's scale alone
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    updates, _ = trainer.tx.update(zeros, trainer.tx.init(params), params)
    assert float(jnp.abs(updates["lm"]["head"]).max()) > 0
    assert float(jnp.abs(updates["lm"]["final_norm"]).max()) == 0


def test_optimizer_is_the_references_clipped_adamw():
    """Three steps of `LoopLMTrainer.tx` on gradients whose global norm is
    far over the clip, then under it, then over it again, so the clip, both
    moments, their bias corrections, the decay and its mask all show, against
    benchmark/reference_lm.py `adamw_step` written out leaf by leaf."""
    import optax
    from benchmark import reference_lm as R
    trainer = make_trainer(**{"training.dtype": "float32"})
    params = trainer.init_state(2, seed=5).params
    lr, wd = (float(trainer.config[k]) for k in ("lr.lm_lr",
                                                 "lr.weight_decay"))
    name = lambda path: str(path[-1].key)   # noqa: E731
    opt_state = trainer.tx.init(params)
    want = params
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    m, v = zeros, zeros
    for t, size in enumerate((1.0, 1e-4, 0.05), start=1):
        grads = jax.tree_util.tree_map(
            lambda p, k: size * jax.random.normal(k, p.shape), params,
            jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(params), list(jax.random.split(
                    jax.random.PRNGKey(t), len(jax.tree_util.tree_leaves(
                        params))))))
        updates, opt_state = trainer.tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        scale = R.clip_scale(R.global_norm(grads))
        assert (float(scale) < 1.0) == (size != 1e-4)
        stepped = jax.tree_util.tree_map_with_path(
            lambda path, p, g, m_, v_: R.adamw_step(
                p, scale * g, m_, v_, t, lr, wd, R.decayed(name(path))),
            want, grads, m, v)
        pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
            lambda _, s: s[i], want, stepped)
        want, m, v = pick(0), pick(1), pick(2)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(params)[0],
                jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-6, atol=1e-9, err_msg=str(path))


# ---------------- the normal path ----------------

def _run_train_cli(tmp_path, extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "train_cli.py"), "--config_path",
         os.path.join(CONFIG_DIR, "params_ouro_2p6b.yaml"), "--workspace",
         str(tmp_path), "--version", "v", "--extra_config",
         json.dumps(extra)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)


def test_train_cli_trains_checkpoints_and_resumes(tmp_path):
    extra = dict(TINY, **{"training.dtype": "float32", "training.epochs": 2,
                          "lr.lm_lr": 3e-3})
    proc = _run_train_cli(tmp_path, extra)
    assert proc.returncode == 0, proc.stderr[-3000:]
    log = open(os.path.join(str(tmp_path), "v", "training.log")).read()
    assert "LoopLMTrainer" in log and "ce by pass" in log
    # data/tokens.py CORPUS_ROWS = 64 rows, 2 a step: 32 steps an epoch
    assert "Final checkpoint saved at step 64" in log
    losses = [float(x.split("=")[1].split()[0])
              for x in log.split("total_loss")[1:]]
    assert len(losses) == 32 and losses[-1] < losses[0]
    # spans of the shared feed and step, and the family's gauges, reached
    # the run's event stream
    events = open(os.path.join(str(tmp_path), "v", "events.jsonl")).read()
    for name in ("train.step.dispatch", "data.stage.h2d",
                 "data.assemble.batch", "train.lm.tokens_per_s",
                 "train.lm.exit_q_mean.4", "data.pack.slots"):
        assert name in events, name
    again = _run_train_cli(tmp_path, dict(extra, **{"training.epochs": 3}))
    assert again.returncode == 0, again.stderr[-3000:]
    log = open(os.path.join(str(tmp_path), "v", "training.log")).read()
    assert "Resumed from checkpoint at step 64" in log
    assert "Final checkpoint saved at step 96" in log


@pytest.mark.parametrize("entry", ["import_mine_tpu", "train_cli_mine",
                                   "serve_cli"])
def test_mine_paths_import_no_module_of_the_new_family(entry, tmp_path):
    """`import mine_tpu`, train_cli.py on a MINE YAML and serve_cli.py load
    not one module of the looped language model."""
    new = ("mine_tpu.models.looplm", "mine_tpu.kernels.attention",
           "mine_tpu.train.lm_step", "mine_tpu.train.lm_loss",
           "mine_tpu.data.tokens")
    tiny_mine = {"data.name": "synthetic", "data.img_h": 64, "data.img_w": 64,
                 "mpi.num_bins_coarse": 4, "model.num_layers": 18,
                 "data.per_gpu_batch_size": 2, "data.visible_point_count": 32,
                 "training.epochs": 1, "data.num_seq_per_gpu": 2}
    body = {
        "import_mine_tpu": "import mine_tpu",
        "train_cli_mine": (
            "import sys, runpy; sys.argv = ['train_cli.py', '--config_path', "
            "%r, '--workspace', %r, '--version', 'v', '--extra_config', %r]; "
            "runpy.run_path(%r, run_name='__main__')" % (
                os.path.join(CONFIG_DIR, "params_llff.yaml"), str(tmp_path),
                json.dumps(tiny_mine),
                os.path.join(ROOT, "train_cli.py"))),
        "serve_cli": (
            "import sys, runpy; sys.argv = ['serve_cli.py', '--help']\n"
            "try:\n    runpy.run_path(%r, run_name='__main__')\n"
            "except SystemExit:\n    pass" % os.path.join(ROOT,
                                                         "serve_cli.py")),
    }[entry]
    code = (body + "\nimport sys\nloaded = [m for m in %r if m in "
            "sys.modules]\nassert not loaded, loaded\nprint('CLEAN')" % (new,))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0 and "CLEAN" in proc.stdout, proc.stderr[-2000:]
