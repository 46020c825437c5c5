"""Config-branch coverage for the jitted train step: coarse-to-fine plane
refinement, alpha compositing mode, DTU background-depth mode, remat."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mine_tpu.data.synthetic import make_batch
from mine_tpu.train.step import SynthesisTrainer
from tests.test_train import tiny_config, to_jnp


def _one_step(cfg, batch_size=1):
    trainer = SynthesisTrainer(cfg, steps_per_epoch=10)
    state = trainer.init_state(batch_size=batch_size)
    batch = to_jnp(make_batch(batch_size, 64, 64, num_points=16))
    state, metrics = trainer.train_step(state, batch)
    return state, {k: float(v) for k, v in metrics.items()}


def test_decoder_plane_chunks_step_close_to_unchunked():
    """training.decoder_plane_chunks=2: the full train step runs and lands
    near the unchunked loss. Not exact by design — each chunk normalizes by
    its own BN batch statistics (ghost BN over B*S/chunks, models/mpi.py) —
    so the tolerance is loose enough for BN-stat drift but tight enough to
    catch mis-wired chunk plumbing."""
    cfg = tiny_config()
    cfg["mpi.num_bins_coarse"] = 4
    _, m0 = _one_step(cfg)
    cfg_c = dict(cfg)
    cfg_c["training.decoder_plane_chunks"] = 2
    _, m1 = _one_step(cfg_c)
    assert np.isfinite(m1["loss"]), m1
    np.testing.assert_allclose(m1["loss"], m0["loss"], rtol=0.05)


def test_coarse_to_fine_step():
    """mpi.num_bins_fine > 0: importance-sampled extra planes, static shapes
    (mpi_rendering.predict_mpi_coarse_to_fine :244-271)."""
    cfg = tiny_config()
    cfg["mpi.num_bins_fine"] = 3
    state, m = _one_step(cfg)
    assert np.isfinite(m["loss"]), m
    assert m["loss_rgb_tgt"] > 0


def test_use_alpha_mode_step():
    cfg = tiny_config()
    cfg["mpi.use_alpha"] = True
    _, m = _one_step(cfg)
    assert np.isfinite(m["loss"]), m


def test_bg_depth_inf_dtu_mode_step():
    """DTU config shape: is_bg_depth_inf + no disparity loss/scale factor
    (synthesis_task.py:213-214, weighted_sum_mpi :74-77)."""
    cfg = tiny_config()
    cfg["data.name"] = "dtu"
    cfg["mpi.is_bg_depth_inf"] = True
    cfg["mpi.valid_mask_threshold"] = 0
    _, m = _one_step(cfg)
    assert np.isfinite(m["loss"]), m
    assert m["loss_disp_pt3dsrc"] == 0.0  # disp loss disabled for dtu
    assert m["loss_disp_pt3dtgt"] == 0.0


def test_remat_step_matches_no_remat():
    """training.remat rematerializes the model in backward — same numbers
    for every checkpoint policy (false | true | dots | dots_no_batch)."""
    cfg = tiny_config()
    t_plain = SynthesisTrainer(cfg, steps_per_epoch=10)
    batch = to_jnp(make_batch(1, 64, 64, num_points=16))
    s0 = t_plain.init_state(batch_size=1)
    s0_after, m0 = t_plain.train_step(s0, batch)
    # post-step params exercise the policy-dependent BACKWARD pass (the
    # forward loss alone cannot distinguish checkpoint policies)
    p0_after = [np.array(x)
                for x in jax.tree_util.tree_leaves(s0_after.params)]

    for policy in (True, "dots", "dots_no_batch"):
        cfg_r = dict(cfg)
        cfg_r["training.remat"] = policy
        t_remat = SynthesisTrainer(cfg_r, steps_per_epoch=10)
        s1 = t_remat.init_state(batch_size=1)
        s1_after, m1 = t_remat.train_step(s1, batch)
        np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                                   rtol=1e-4, err_msg=str(policy))
        # Adam's grad/sqrt(v) normalization turns low-order recompute-order
        # noise into up-to-full-step (~lr) flips on isolated near-zero-grad
        # elements, so a per-element tolerance cannot separate fp noise from
        # real error. Distributional check instead: a mis-wired backward
        # changes update DIRECTIONS en masse, fp noise touches ~1e-5 of
        # elements (observed: 1-2 per 6e5).
        flat_a = np.concatenate(
            [np.asarray(x).ravel()
             for x in jax.tree_util.tree_leaves(s1_after.params)])
        flat_b = np.concatenate([b.ravel() for b in p0_after])
        frac = float(np.mean(np.abs(flat_a - flat_b) > 1e-4))
        assert frac < 1e-3, (policy, frac)


def test_smoothness_terms_enabled():
    """Non-zero smoothness lambdas engage the edge-aware terms (realestate
    config shape)."""
    cfg = tiny_config()
    cfg["loss.smoothness_lambda_v1"] = 0.5
    cfg["loss.smoothness_lambda_v2"] = 0.01
    _, m = _one_step(cfg)
    assert np.isfinite(m["loss"]), m
    assert m["loss_smooth_tgt"] != 0.0
    assert m["loss_smooth_tgt_v2"] != 0.0


def test_pallas_diff_composite_matches_xla_training():
    """training.composite_backend=pallas_diff: one full train step must match
    the XLA-composite step numerically (fwd via the fused kernel, bwd via the
    custom-VJP kernel; interpret mode on CPU)."""
    cfg = tiny_config()
    batch = to_jnp(make_batch(1, 64, 64, num_points=16))
    t_xla = SynthesisTrainer(cfg, steps_per_epoch=10)
    s0 = t_xla.init_state(batch_size=1)
    _, m_xla = t_xla.train_step(s0, batch)

    cfg_p = dict(cfg)
    cfg_p["training.composite_backend"] = "pallas_diff"
    t_pal = SynthesisTrainer(cfg_p, steps_per_epoch=10)
    s1 = t_pal.init_state(batch_size=1)
    # snapshot before the step: the jitted step donates its input state
    p_before = [np.array(x) for x in jax.tree_util.tree_leaves(s1.params)]
    s2, m_pal = t_pal.train_step(s1, batch)

    np.testing.assert_allclose(float(m_pal["loss"]), float(m_xla["loss"]),
                               rtol=1e-4)
    np.testing.assert_allclose(float(m_pal["loss_rgb_tgt"]),
                               float(m_xla["loss_rgb_tgt"]), rtol=1e-4)
    # parameters actually moved under the pallas backward
    moved = [float(np.abs(np.asarray(a) - b).max())
             for a, b in zip(jax.tree_util.tree_leaves(s2.params), p_before)]
    assert max(moved) > 0


@pytest.fixture(scope="module")
def warp_backend_steps():
    """One full train step on the same state and batch under each
    training.warp_backend: (xla metrics, pallas_diff metrics, the largest
    parameter change of the pallas_diff step)."""
    cfg = tiny_config()
    batch = to_jnp(make_batch(1, 64, 64, num_points=16))
    t_xla = SynthesisTrainer(cfg, steps_per_epoch=10)
    s0 = t_xla.init_state(batch_size=1)
    _, m_xla = t_xla.train_step(s0, batch)

    cfg_w = dict(cfg)
    cfg_w["training.warp_backend"] = "pallas_diff"
    t_w = SynthesisTrainer(cfg_w, steps_per_epoch=10)
    s1 = t_w.init_state(batch_size=1)
    p_before = [np.array(x) for x in jax.tree_util.tree_leaves(s1.params)]
    s2, m_w = t_w.train_step(s1, batch)
    moved = [float(np.abs(np.asarray(a) - b).max())
             for a, b in zip(jax.tree_util.tree_leaves(s2.params), p_before)]
    return m_xla, m_w, max(moved)


def test_pallas_diff_warp_matches_xla_training(warp_backend_steps):
    """training.warp_backend=pallas_diff: one full train step through the
    banded warp (fwd kernel + transposed-band VJP kernel, interpret mode on
    CPU) must match the gather-path step numerically (VERDICT r1 item 3)."""
    m_xla, m_w, moved = warp_backend_steps
    np.testing.assert_allclose(float(m_w["loss"]), float(m_xla["loss"]),
                               rtol=1e-4)
    np.testing.assert_allclose(float(m_w["loss_rgb_tgt"]),
                               float(m_xla["loss_rgb_tgt"]), rtol=1e-4)
    assert moved > 0


def test_warp_diagnostics_are_the_guarded_backends_alone(warp_backend_steps):
    """The step's warp_fallback_frac and warp_subband_frac are shares in
    [0, 1] under pallas_diff (means over the loss scales of
    homography_warp's diagnostics) and are not reported at all under xla,
    whose NaN would otherwise reach the log line and the gauges."""
    m_xla, m_w, _ = warp_backend_steps
    for key in ("warp_fallback_frac", "warp_subband_frac"):
        assert key not in m_xla
        assert 0.0 <= float(m_w[key]) <= 1.0, (key, float(m_w[key]))
    # some loss scale ran the kernels, and counted their windows
    assert float(m_w["warp_fallback_frac"]) < 1.0
    assert float(m_w["warp_subband_frac"]) > 0.0


def test_sigma_dropout_step():
    """model.sigma_dropout_rate drops whole planes during training; the step
    stays finite and the dropout rng is threaded (depth_decoder.py:143-144)."""
    cfg = tiny_config()
    cfg["model.sigma_dropout_rate"] = 0.3
    _, m = _one_step(cfg)
    assert np.isfinite(m["loss"]), m
