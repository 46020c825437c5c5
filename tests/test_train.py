"""Trainer integration: loss graph wiring, optimizer semantics, an overfit
run on a synthetic scene, and multi-device sharding on the fake CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mine_tpu.config import CONFIG_DIR, load_config, mpi_config_from_dict
from mine_tpu.data.synthetic import SyntheticMPIDataset, make_batch
from mine_tpu.train.state import current_lrs, make_optimizer, multistep_lr
from mine_tpu.train.step import SynthesisTrainer, sample_disparity


def tiny_config(**overrides):
    import os

    cfg = load_config(os.path.join(CONFIG_DIR, "params_default.yaml"))
    cfg.update({
        "data.name": "llff",
        "data.img_h": 64, "data.img_w": 64,
        "data.per_gpu_batch_size": 1,
        "mpi.num_bins_coarse": 4,
        "mpi.disparity_start": 1.0, "mpi.disparity_end": 0.2,
        "model.num_layers": 18,
        "lr.backbone_lr": 1e-3, "lr.decoder_lr": 1e-3,
        "lr.decay_steps": [1000],
        "loss.smoothness_lambda_v1": 0.0,
        "loss.smoothness_lambda_v2": 0.0,
        "training.dtype": "float32",
    })
    cfg.update(overrides)
    return cfg


def to_jnp(batch):
    return {k: jnp.asarray(v) for k, v in batch.items()}


def test_multistep_lr_schedule():
    sched = multistep_lr(1.0, [2, 4], 0.1, steps_per_epoch=10)
    assert float(sched(0)) == 1.0
    assert float(sched(19)) == 1.0
    np.testing.assert_allclose(float(sched(20)), 0.1, rtol=1e-6)
    np.testing.assert_allclose(float(sched(40)), 0.01, rtol=1e-6)
    lrs = current_lrs({"lr.backbone_lr": 1.0, "lr.decoder_lr": 2.0,
                       "lr.decay_gamma": 0.1, "lr.decay_steps": [2, 4]},
                      steps_per_epoch=10, step=25)
    np.testing.assert_allclose(lrs["backbone"], 0.1)
    np.testing.assert_allclose(lrs["decoder"], 0.2)


def test_multistep_lr_accum_boundaries():
    """With grad accumulation the decay boundary is the ROUNDED product
    e*steps_per_epoch//accum, not e*(steps_per_epoch//accum) — when accum
    does not divide steps_per_epoch the truncated form fires the decay
    early relative to the host micro-step clock (ADVICE r2)."""
    # steps_per_epoch=10, accum=3: epoch-2 milestone = 20 micro = 6 opt steps
    # (truncated per-epoch form would give 2*(10//3)=6 here too; epoch 4
    # separates them: 40//3=13 vs 4*3=12)
    sched = multistep_lr(1.0, [2, 4], 0.1, steps_per_epoch=10, accum=3)
    np.testing.assert_allclose(float(sched(5)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(float(sched(6)), 0.1, rtol=1e-6)
    np.testing.assert_allclose(float(sched(12)), 0.1, rtol=1e-6)
    np.testing.assert_allclose(float(sched(13)), 0.01, rtol=1e-6)
    # accum > steps_per_epoch: milestones 3 and 6 micro-steps both precede
    # the first optimizer step (8 micro) -> gammas compound on one boundary
    # instead of one silently overwriting the other
    sched2 = multistep_lr(1.0, [1, 2], 0.1, steps_per_epoch=3, accum=8)
    np.testing.assert_allclose(float(sched2(1)), 0.01, rtol=1e-6)
    # host-side readback (micro-step clock) must agree with the device
    # schedule (optimizer-step clock) at EVERY micro-step, any accum
    for accum, spe, miles in ((8, 3, [1, 2]), (3, 10, [2, 4]), (1, 10, [2, 4])):
        cfg = {"lr.backbone_lr": 1.0, "lr.decoder_lr": 1.0,
               "lr.decay_gamma": 0.1, "lr.decay_steps": miles,
               "training.grad_accum_steps": accum}
        sched_a = multistep_lr(1.0, miles, 0.1, steps_per_epoch=spe,
                               accum=accum)
        for micro in range(0, 50):
            dev = float(sched_a(micro // accum))
            host = current_lrs(cfg, spe, micro)["backbone"]
            np.testing.assert_allclose(host, dev, rtol=1e-5,
                                       err_msg=f"accum={accum} micro={micro}")


def test_optimizer_matches_torch_adam():
    """One Adam step with weight decay must match torch.optim.Adam (the
    reference optimizer, synthesis_task.py:83-87)."""
    import torch

    w0 = np.array([1.0, -2.0, 3.0], dtype=np.float32)
    g0 = np.array([0.1, 0.2, -0.3], dtype=np.float32)
    lr, wd = 1e-3, 4e-5

    t_w = torch.tensor(w0, requires_grad=True)
    opt = torch.optim.Adam([t_w], lr=lr, weight_decay=wd)
    t_w.grad = torch.tensor(g0)
    opt.step()
    t_w.grad = torch.tensor(g0 * 0.5)
    opt.step()

    config = {"lr.backbone_lr": lr, "lr.decoder_lr": lr * 7,
              "lr.weight_decay": wd, "lr.decay_gamma": 0.1,
              "lr.decay_steps": []}
    tx = make_optimizer(config, steps_per_epoch=100)
    params = {"backbone": {"w": jnp.asarray(w0)},
              "decoder": {"w": jnp.asarray(w0)}}
    opt_state = tx.init(params)
    for scale in (1.0, 0.5):
        grads = {"backbone": {"w": jnp.asarray(g0 * scale)},
                 "decoder": {"w": jnp.asarray(g0 * scale)}}
        updates, opt_state = tx.update(grads, opt_state, params)
        import optax
        params = optax.apply_updates(params, updates)

    np.testing.assert_allclose(np.asarray(params["backbone"]["w"]),
                               t_w.detach().numpy(), rtol=1e-5, atol=1e-7)
    # decoder group uses its own (7x) LR -> must differ
    assert not np.allclose(np.asarray(params["decoder"]["w"]),
                           np.asarray(params["backbone"]["w"]))


def test_sample_disparity_modes():
    cfg = mpi_config_from_dict({"mpi.num_bins_coarse": 4,
                                "mpi.disparity_start": 1.0,
                                "mpi.disparity_end": 0.2,
                                "mpi.fix_disparity": True})
    d = sample_disparity(jax.random.PRNGKey(0), 2, cfg)
    np.testing.assert_allclose(np.asarray(d[0]), np.linspace(1.0, 0.2, 4),
                               rtol=1e-6)
    cfg2 = mpi_config_from_dict({"mpi.num_bins_coarse": 3,
                                 "mpi.disparity_list": [1.0, 0.6, 0.3, 0.1]})
    d2 = np.asarray(sample_disparity(jax.random.PRNGKey(1), 4, cfg2))
    assert d2.shape == (4, 3)
    assert np.all(d2[:, 0] <= 1.0) and np.all(d2[:, 0] >= 0.6)


def test_synthetic_dataset_geometry():
    """View 0 has the identity pose, so its render must equal the canonical
    MPI composite; points must reproject into the image."""
    ds = SyntheticMPIDataset(seed=0, height=32, width=32, num_views=3,
                             num_points=16)
    batch = ds.pair_batch([(0, 1)])
    assert batch["src_img"].shape == (1, 32, 32, 3)
    # pt3d in front of the camera, reprojecting inside the image
    for v in range(3):
        xyz = ds.pt3d[v]
        assert np.all(xyz[2] > 0)
        pix = ds.K @ xyz
        pix = pix[:2] / pix[2:]
        assert pix[0].min() >= -1 and pix[0].max() <= 32
    # depth within the ground-truth plane range
    assert 0.9 <= ds.depths[0].min() <= ds.depths[0].max() <= 5.1


def test_train_step_runs_and_updates():
    cfg = tiny_config()
    trainer = SynthesisTrainer(cfg, steps_per_epoch=10)
    state = trainer.init_state(batch_size=1)
    batch = to_jnp(make_batch(1, 64, 64, num_points=16))

    p0 = jax.tree_util.tree_leaves(state.params)[0].copy()
    state2, metrics = trainer.train_step(state, batch)
    assert int(state2.step) == 1
    m = {k: float(v) for k, v in metrics.items()}
    assert np.isfinite(m["loss"]), m
    assert m["loss_rgb_tgt"] > 0
    p1 = jax.tree_util.tree_leaves(state2.params)[0]
    assert np.abs(np.asarray(p1) - np.asarray(p0)).max() > 0


def test_eval_step_runs():
    cfg = tiny_config()
    trainer = SynthesisTrainer(cfg, steps_per_epoch=10)
    state = trainer.init_state(batch_size=1)
    batch = to_jnp(make_batch(1, 64, 64, num_points=16))
    metrics, visuals = trainer.eval_step(state, batch, jax.random.PRNGKey(9))
    assert np.isfinite(float(metrics["loss"]))
    # gated: no weights -> NaN, never a fake perfect 0.0 (VERDICT r1 weak 5)
    assert np.isnan(float(metrics["lpips_tgt"]))
    assert visuals["tgt_imgs_syn"].shape == (1, 3, 64, 64)
    assert visuals["tgt_mask_syn"].shape == (1, 1, 64, 64)


@pytest.mark.slow
def test_overfit_synthetic_scene():
    """SURVEY.md section 7 step 2: the end-to-end slice must overfit one
    synthetic scene — loss down, PSNR up."""
    cfg = tiny_config()
    # fixed plane disparities: deterministic loss, clean overfit signal
    cfg["mpi.fix_disparity"] = True
    trainer = SynthesisTrainer(cfg, steps_per_epoch=1000)
    state = trainer.init_state(batch_size=1)
    ds = SyntheticMPIDataset(seed=0, height=64, width=64, num_views=2,
                             num_points=16)
    batch = to_jnp(ds.pair_batch([(0, 1)]))

    losses, psnrs = [], []
    for i in range(60):
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss_rgb_tgt"])
                      + float(metrics["loss_ssim_tgt"]))
        psnrs.append(float(metrics["psnr_tgt"]))
    first, last = np.mean(losses[:3]), np.mean(losses[-3:])
    assert np.isfinite(last)
    assert last < 0.75 * first, (first, last)
    assert np.mean(psnrs[-3:]) > np.mean(psnrs[:3]) + 0.5, (psnrs[:3], psnrs[-3:])


def test_train_step_sharded_matches_single_device():
    """Same math on the 8-device ('data','plane') mesh: runs, and the loss
    matches the unsharded step (GSPMD = SyncBN + DDP semantics)."""
    from mine_tpu.parallel.mesh import make_mesh

    assert len(jax.devices()) == 8, "conftest must provide 8 CPU devices"
    cfg = tiny_config()
    cfg["data.per_gpu_batch_size"] = 4
    batch = to_jnp(make_batch(4, 64, 64, num_points=16))

    t_single = SynthesisTrainer(cfg, steps_per_epoch=10)
    s0 = t_single.init_state(batch_size=4)
    _, m_single = t_single.train_step(s0, batch)

    mesh = make_mesh(data=4, plane=2)
    t_mesh = SynthesisTrainer(cfg, mesh=mesh, steps_per_epoch=10)
    s1 = t_mesh.init_state(batch_size=4)
    s2, m_mesh = t_mesh.train_step(s1, batch)

    assert np.isfinite(float(m_mesh["loss"]))
    np.testing.assert_allclose(float(m_mesh["loss"]), float(m_single["loss"]),
                               rtol=2e-3)
    # second step exercises donated buffers + updated stats
    _, m2 = t_mesh.train_step(s2, batch)
    assert np.isfinite(float(m2["loss"]))


def test_eval_step_masked_sharded_matches_single_device():
    """The masked (padded-tail) eval jit on the 8-device mesh — the exact
    program multi-host run_eval executes — must match the unsharded masked
    eval: batch AND the [B] validity weight shard over 'data'."""
    from mine_tpu.parallel.mesh import make_mesh

    assert len(jax.devices()) == 8, "conftest must provide 8 CPU devices"
    cfg = tiny_config()
    cfg["data.per_gpu_batch_size"] = 4
    batch = to_jnp(make_batch(4, 64, 64, num_points=16))
    w = jnp.asarray([1.0, 1.0, 0.0, 1.0], jnp.float32)  # one padded slot
    key = jax.random.PRNGKey(5)

    t_single = SynthesisTrainer(cfg, steps_per_epoch=10)
    s0 = t_single.init_state(batch_size=4)
    m_single = {k: float(v) for k, v in
                t_single.eval_step_masked(s0, batch, key, w).items()}

    mesh = make_mesh(data=4, plane=2)
    t_mesh = SynthesisTrainer(cfg, mesh=mesh, steps_per_epoch=10)
    s1 = t_mesh.init_state(batch_size=4)
    batch_m = t_mesh.put_batch({k: np.asarray(v) for k, v in batch.items()})
    w_m = t_mesh.put_example_array(np.asarray(w))
    m_mesh = {k: float(v) for k, v in
              t_mesh.eval_step_masked(s1, batch_m, key, w_m).items()}

    for k in m_single:
        if np.isnan(m_single[k]):  # lpips sentinel
            assert np.isnan(m_mesh[k]), k
            continue
        np.testing.assert_allclose(m_mesh[k], m_single[k], rtol=2e-3,
                                   err_msg=k)


@pytest.mark.slow
def test_plane_chunked_decoder_composes_with_mesh():
    """decoder_plane_chunks (memory) x plane-sharded mesh (parallelism) —
    the pod configuration for big batches: each chunk's B*S/k block still
    shards over ('data','plane') and the step lands near the unchunked
    mesh step (ghost-BN drift only)."""
    from mine_tpu.parallel.mesh import make_mesh

    cfg = tiny_config()
    cfg["data.per_gpu_batch_size"] = 4
    cfg["mpi.num_bins_coarse"] = 8
    batch = to_jnp(make_batch(4, 64, 64, num_points=16))
    mesh = make_mesh(data=4, plane=2)

    t_plain = SynthesisTrainer(cfg, mesh=mesh, steps_per_epoch=10)
    s0 = t_plain.init_state(batch_size=4)
    _, m_plain = t_plain.train_step(s0, batch)

    cfg_c = dict(cfg)
    cfg_c["training.decoder_plane_chunks"] = 2  # chunk size 4, plane 2 | 4
    t_chunk = SynthesisTrainer(cfg_c, mesh=mesh, steps_per_epoch=10)
    s1 = t_chunk.init_state(batch_size=4)
    _, m_chunk = t_chunk.train_step(s1, batch)

    assert np.isfinite(float(m_chunk["loss"]))
    np.testing.assert_allclose(float(m_chunk["loss"]),
                               float(m_plain["loss"]), rtol=0.05)


def test_train_step_pallas_backends_on_mesh():
    """pallas_diff composite + warp compose with the multi-device mesh via
    shard_map (VERDICT r1 item 4 — the single-device guard is gone): the
    mesh step must match the single-device XLA step numerically."""
    from mine_tpu.parallel.mesh import make_mesh

    cfg = tiny_config()
    cfg["data.per_gpu_batch_size"] = 4
    batch = to_jnp(make_batch(4, 64, 64, num_points=16))

    t_ref = SynthesisTrainer(cfg, steps_per_epoch=10)
    s0 = t_ref.init_state(batch_size=4)
    _, m_ref = t_ref.train_step(s0, batch)

    cfg_p = dict(cfg)
    cfg_p["training.composite_backend"] = "pallas_diff"
    cfg_p["training.warp_backend"] = "pallas_diff"
    mesh = make_mesh(data=4, plane=2)
    t_mesh = SynthesisTrainer(cfg_p, mesh=mesh, steps_per_epoch=10)
    s1 = t_mesh.init_state(batch_size=4)
    p_before = [np.array(x) for x in jax.tree_util.tree_leaves(s1.params)]
    s2, m_mesh = t_mesh.train_step(s1, batch)

    assert np.isfinite(float(m_mesh["loss"]))
    np.testing.assert_allclose(float(m_mesh["loss"]), float(m_ref["loss"]),
                               rtol=2e-3)
    p_moved = [float(np.abs(np.asarray(a) - b).max())
               for a, b in zip(jax.tree_util.tree_leaves(s2.params), p_before)]
    assert max(p_moved) > 0


def test_grad_accum_matches_single_step_on_identical_micro_batches():
    """training.grad_accum_steps=2 (optax.MultiSteps around the two-group
    Adam): two train_steps over the SAME micro-batch must produce exactly
    one single-step update — params frozen after the first (zero update
    emitted mid-window), then updated with the mean gradient, which with
    mpi.fix_disparity (no per-micro RNG) and no dropout equals the
    single-batch gradient. Train-mode BN normalizes with current-batch
    statistics, so running-stats updates between micro-steps cannot change
    gradients."""
    overrides = {"training.grad_accum_steps": 2, "mpi.fix_disparity": True}
    batch = to_jnp(make_batch(1, 64, 64, num_points=16))

    trainer = SynthesisTrainer(tiny_config(**overrides), steps_per_epoch=10)
    assert trainer.grad_accum_steps == 2
    state = trainer.init_state(batch_size=1, seed=3)
    p0 = [np.asarray(x).copy()
          for x in jax.tree_util.tree_leaves(state.params)]

    state, m0 = trainer.train_step(state, batch)
    assert int(state.step) == 1  # step stays in micro-batch units
    for a, b in zip(jax.tree_util.tree_leaves(state.params), p0):
        np.testing.assert_array_equal(np.asarray(a), b)  # mid-window: frozen

    state, m1 = trainer.train_step(state, batch)
    np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]), rtol=1e-6)

    ref_trainer = SynthesisTrainer(tiny_config(**{"mpi.fix_disparity": True}),
                                   steps_per_epoch=10)
    ref_state = ref_trainer.init_state(batch_size=1, seed=3)
    ref_state, _ = ref_trainer.train_step(ref_state, batch)

    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(ref_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
