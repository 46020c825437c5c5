"""PR-2 fused loss-pyramid pass: the restructured loss graph (shared
ScalePlan + stacked ssim_pairs, train/loss.py) must be numerically identical
to the old per-scale formulation it replaced.

`_ref_*` below is a frozen copy of the pre-refactor path: per-scale strided
slicing of the full-res images, per-scale intrinsics/grid derivation, two
independent `ssim()` calls, and inline edge-mask/image-gradient computation
in every edge_aware call — kept here as the ground truth the acceptance
criterion compares against ("loss sequences identical (<=1e-6, CPU) to the
current per-scale path over a multi-step train run"). It reuses the
unchanged private helpers from train/loss.py (_safe_log & co.) and the
(bitwise-identical, tested below) single-pair `ssim()`; what it does NOT use
is the ScalePlan, ssim_pairs stacking, or precomputed masks/grads.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mine_tpu import geometry
from mine_tpu.config import CONFIG_DIR, load_config
from mine_tpu.data.synthetic import make_batch
from mine_tpu.losses import (edge_aware_loss, edge_aware_loss_v2, psnr, ssim,
                             ssim_pairs)
from mine_tpu.ops import rendering, sampling
from mine_tpu.train import loss as loss_mod
from mine_tpu.train.step import SynthesisTrainer, sample_disparity

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import dtype_audit  # noqa: E402


# ---------------------------------------------------------------------------
# frozen pre-refactor reference path
# ---------------------------------------------------------------------------

def _ref_ssim(img1, img2, window_size=11, sigma=1.5, size_average=True,
              precision=None):
    """Old ssim(), verbatim dispatch: FIVE separate `_blur` calls (x, y, x²,
    y², xy), 10 Toeplitz einsums per evaluation — the shape the fused
    ssim_pairs replaced. Precision mapping matches the old `_blur` header
    (None -> HIGHEST, "default" -> None)."""
    from mine_tpu.losses.ssim import _blur, resolve_precision
    prec = resolve_precision(precision)
    x = jnp.transpose(img1, (0, 2, 3, 1)).astype(jnp.float32)
    y = jnp.transpose(img2, (0, 2, 3, 1)).astype(jnp.float32)

    mu1 = _blur(x, window_size, sigma, prec)
    mu2 = _blur(y, window_size, sigma, prec)
    e_xx = _blur(x * x, window_size, sigma, prec)
    e_yy = _blur(y * y, window_size, sigma, prec)
    e_xy = _blur(x * y, window_size, sigma, prec)

    mu1_sq = mu1 * mu1
    mu2_sq = mu2 * mu2
    mu1_mu2 = mu1 * mu2
    sigma1_sq = e_xx - mu1_sq
    sigma2_sq = e_yy - mu2_sq
    sigma12 = e_xy - mu1_mu2

    c1, c2 = 0.01 ** 2, 0.03 ** 2
    ssim_map = ((2 * mu1_mu2 + c1) * (2 * sigma12 + c2)) / (
        (mu1_sq + mu2_sq + c1) * (sigma1_sq + sigma2_sq + c2))
    per_image = jnp.mean(ssim_map, axis=(1, 2, 3))
    return jnp.mean(per_image) if size_average else per_image


def _ref_loss_per_scale(scale, mpi, disparity, batch, G_tgt_src, cfg,
                        scale_factor, example_weight=None):
    """Old loss_per_scale, verbatim modulo: mesh/is_val/lpips plumbing
    dropped (untested here, and `constrain` without a mesh is a no-op), and
    the old two-layer precision translation kept exactly as it was."""
    f = 2 ** scale
    src_imgs = loss_mod.nchw(batch["src_img"])[:, :, ::f, ::f]
    tgt_imgs = loss_mod.nchw(batch["tgt_img"])[:, :, ::f, ::f]
    B, _, Hs, Ws = src_imgs.shape

    K_src = geometry.scale_intrinsics(batch["K_src"], scale)
    K_tgt = geometry.scale_intrinsics(batch["K_tgt"], scale)
    K_src_inv = geometry.inverse_intrinsics(K_src)

    grid = geometry.cached_pixel_grid(Hs, Ws)
    xyz_src = geometry.plane_xyz_src(grid, disparity, K_src_inv)

    mpi_rgb = mpi[:, :, 0:3]
    mpi_sigma = mpi[:, :, 3:4]

    src_syn, src_depth, blend_weights, weights = rendering.render(
        mpi_rgb, mpi_sigma, xyz_src,
        use_alpha=cfg.use_alpha, is_bg_depth_inf=cfg.is_bg_depth_inf)
    if cfg.src_rgb_blending:
        mpi_rgb = blend_weights * src_imgs[:, None] \
            + (1.0 - blend_weights) * mpi_rgb
        src_syn, src_depth = rendering.weighted_sum_mpi(
            mpi_rgb, xyz_src, weights, is_bg_depth_inf=cfg.is_bg_depth_inf)

    src_disp_syn = loss_mod._safe_reciprocal_depth(src_depth)

    if cfg.use_disparity_loss or cfg.use_scale_factor:
        src_pt3d = batch["pt3d_src"]
        src_pt_disp = 1.0 / src_pt3d[:, 2:3]
        src_pt_pxpy = loss_mod._project_points(K_src, src_pt3d)
        src_pt_disp_syn = sampling.gather_pixel_by_pxpy(src_disp_syn,
                                                        src_pt_pxpy)
    if scale_factor is None:
        if cfg.use_scale_factor:
            scale_factor = loss_mod.compute_scale_factor(src_pt_disp_syn,
                                                         src_pt_disp)
        else:
            scale_factor = jnp.ones((B,), jnp.float32)

    t_scaled = G_tgt_src[:, 0:3, 3] / scale_factor[:, None]
    G_render = jax.lax.stop_gradient(G_tgt_src.at[:, 0:3, 3].set(t_scaled))
    res = rendering.render_tgt_rgb_depth(
        mpi_rgb, mpi_sigma, disparity, G_render, K_src_inv, K_tgt,
        use_alpha=cfg.use_alpha, is_bg_depth_inf=cfg.is_bg_depth_inf,
        backend=cfg.composite_backend, warp_impl=cfg.warp_backend,
        warp_band=cfg.warp_band, warp_dtype=cfg.warp_dtype, mesh=None)
    tgt_syn, tgt_mask = res.rgb, res.mask
    tgt_disp_syn = loss_mod._safe_reciprocal_depth(res.depth)

    zero = jnp.zeros((), jnp.float32)
    if example_weight is None:
        agg = jnp.mean
    else:
        w = example_weight
        w_sum = jnp.maximum(jnp.sum(w), 1e-8)

        def agg(v):
            return jnp.sum(jnp.where(w > 0, v, 0.0) * w) / w_sum

    def pex(x):
        return jnp.mean(x, axis=tuple(range(1, x.ndim)))

    loss_rgb_src = jax.lax.stop_gradient(agg(pex(jnp.abs(src_syn - src_imgs))))
    ssim_prec = cfg.ssim_precision  # the old double translation, verbatim
    if ssim_prec == "highest":
        ssim_prec = None
    loss_ssim_src = jax.lax.stop_gradient(
        agg(1.0 - _ref_ssim(src_syn, src_imgs, size_average=False,
                            precision=ssim_prec)))
    loss_smooth_src = jax.lax.stop_gradient(
        agg(edge_aware_loss(src_imgs, src_disp_syn,
                            gmin=cfg.smoothness_gmin,
                            grad_ratio=cfg.smoothness_grad_ratio,
                            size_average=False)))

    if cfg.use_disparity_loss:
        loss_disp_src = agg(loss_mod._disp_loss(src_pt_disp_syn, src_pt_disp,
                                                scale_factor))
        tgt_pt3d = batch["pt3d_tgt"]
        tgt_pt_disp = 1.0 / tgt_pt3d[:, 2:3]
        tgt_pt_pxpy = loss_mod._project_points(K_tgt, tgt_pt3d)
        tgt_pt_disp_syn = sampling.gather_pixel_by_pxpy(tgt_disp_syn,
                                                        tgt_pt_pxpy)
        loss_disp_tgt = agg(loss_mod._disp_loss(tgt_pt_disp_syn, tgt_pt_disp,
                                                scale_factor))
    else:
        loss_disp_src = zero
        loss_disp_tgt = zero

    valid = (tgt_mask >= cfg.valid_mask_threshold).astype(jnp.float32)
    loss_rgb_tgt = agg(pex(jnp.abs(tgt_syn - tgt_imgs) * valid))
    loss_ssim_tgt = agg(1.0 - _ref_ssim(tgt_syn, tgt_imgs,
                                        size_average=False,
                                        precision=ssim_prec))

    if cfg.smoothness_lambda_v1 != 0.0:
        loss_smooth_tgt = cfg.smoothness_lambda_v1 * agg(edge_aware_loss(
            tgt_imgs, tgt_disp_syn,
            gmin=cfg.smoothness_gmin, grad_ratio=cfg.smoothness_grad_ratio,
            size_average=False))
    else:
        loss_smooth_tgt = zero
    if cfg.smoothness_lambda_v2 != 0.0:
        loss_smooth_src_v2 = cfg.smoothness_lambda_v2 * agg(
            edge_aware_loss_v2(src_imgs, src_disp_syn, size_average=False))
        loss_smooth_tgt_v2 = cfg.smoothness_lambda_v2 * agg(
            edge_aware_loss_v2(tgt_imgs, tgt_disp_syn, size_average=False))
    else:
        loss_smooth_src_v2 = zero
        loss_smooth_tgt_v2 = zero

    psnr_tgt = jax.lax.stop_gradient(
        agg(psnr(tgt_syn, tgt_imgs, size_average=False)))
    lpips_tgt = zero

    loss = (loss_disp_tgt + loss_disp_src + loss_rgb_tgt + loss_ssim_tgt
            + loss_smooth_tgt + loss_smooth_src_v2 + loss_smooth_tgt_v2)

    loss_dict = {
        "loss": loss,
        "loss_rgb_src": loss_rgb_src,
        "loss_ssim_src": loss_ssim_src,
        "loss_disp_pt3dsrc": loss_disp_src,
        "loss_smooth_src": loss_smooth_src,
        "loss_smooth_tgt": loss_smooth_tgt,
        "loss_smooth_src_v2": loss_smooth_src_v2,
        "loss_smooth_tgt_v2": loss_smooth_tgt_v2,
        "loss_rgb_tgt": loss_rgb_tgt,
        "loss_ssim_tgt": loss_ssim_tgt,
        "lpips_tgt": lpips_tgt,
        "psnr_tgt": psnr_tgt,
        "loss_disp_pt3dtgt": loss_disp_tgt,
    }
    if cfg.warp_backend == "pallas_diff":
        loss_dict["warp_fallback"] = jax.lax.stop_gradient(
            1.0 - res.warp_in_domain)
    visuals = {
        "src_disparity_syn": src_disp_syn,
        "tgt_disparity_syn": tgt_disp_syn,
        "tgt_imgs_syn": tgt_syn,
        "tgt_mask_syn": tgt_mask,
        "src_imgs_syn": src_syn,
    }
    return loss_dict, visuals, scale_factor


def _ref_compute_losses(mpi_list, disparity, batch, cfg, example_weight=None):
    """Old compute_losses, verbatim (same aggregation formula)."""
    G_tgt_src = geometry.rigid_inverse(batch["G_src_tgt"])
    scale_factor = None
    dicts = []
    visuals0 = None
    for scale in range(4):
        ld, vis, scale_factor = _ref_loss_per_scale(
            scale, mpi_list[scale], disparity, batch, G_tgt_src, cfg,
            scale_factor, example_weight=example_weight)
        dicts.append(ld)
        if scale == 0:
            visuals0 = vis
    total = dicts[0]["loss"]
    for s in range(1, 4):
        if cfg.use_multi_scale:
            total = total + dicts[s]["loss_rgb_tgt"] + dicts[s]["loss_ssim_tgt"]
        total = (total + dicts[s]["loss_disp_pt3dsrc"]
                 + dicts[s]["loss_disp_pt3dtgt"])
        total = (total + dicts[s]["loss_smooth_src_v2"]
                 + dicts[s]["loss_smooth_tgt_v2"])
    metrics = dict(dicts[0])
    metrics["loss"] = total
    if "warp_fallback" in metrics:
        del metrics["warp_fallback"]
        metrics["warp_fallback_frac"] = jnp.mean(
            jnp.stack([d["warp_fallback"] for d in dicts]))
    return total, metrics, visuals0


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_setup():
    """64x64 / 4-plane / resnet18 trainer with EVERY loss term active (both
    smoothness lambdas nonzero) so the equivalence sweep covers all code
    paths the plan precomputes for."""
    cfg = load_config(os.path.join(CONFIG_DIR, "params_default.yaml"))
    cfg.update({
        "data.name": "llff",
        "data.img_h": 64, "data.img_w": 64,
        "data.per_gpu_batch_size": 2,
        "mpi.num_bins_coarse": 4,
        "mpi.disparity_start": 1.0, "mpi.disparity_end": 0.2,
        "model.num_layers": 18,
        "loss.smoothness_lambda_v1": 0.5,
        "loss.smoothness_lambda_v2": 0.01,
        "training.dtype": "float32",
    })
    trainer = SynthesisTrainer(cfg, steps_per_epoch=100)
    state = trainer.init_state(batch_size=2)
    batch = {k: jnp.asarray(v)
             for k, v in make_batch(2, 64, 64, num_points=64).items()}
    return trainer, state, batch


def _forward_at(trainer, state, batch):
    """Reproduce _grads_and_metrics' exact key plumbing for `state.step`,
    returning the decoder outputs the loss graph consumes."""
    key = jax.random.fold_in(state.rng, state.step)
    d_key, f_key, drop_key = jax.random.split(key, 3)
    B = batch["src_img"].shape[0]
    disparity = sample_disparity(d_key, B, trainer.cfg)
    mpi_list, disparity_all, _ = trainer._forward(
        state.params, state.batch_stats, batch, disparity, f_key, drop_key,
        train=True)
    return mpi_list, disparity_all


# ---------------------------------------------------------------------------
# equivalence: fused pass == frozen per-scale reference
# ---------------------------------------------------------------------------

def test_fused_matches_reference_over_training(tiny_setup):
    """The acceptance criterion: identical loss sequences (<=1e-6) over a
    multi-step train run — params evolve under real optimizer updates, the
    loss is re-evaluated against the frozen reference at every step."""
    trainer, state, batch = tiny_setup
    # train_step donates its input state; step on a copy so the module-scoped
    # fixture's buffers survive for the other tests
    state = jax.tree.map(jnp.copy, state)
    for step in range(3):
        mpi_list, disparity_all = _forward_at(trainer, state, batch)
        t_new, m_new, v_new = loss_mod.compute_losses(
            mpi_list, disparity_all, batch, trainer.cfg)
        t_ref, m_ref, v_ref = _ref_compute_losses(
            mpi_list, disparity_all, batch, trainer.cfg)
        np.testing.assert_allclose(float(t_new), float(t_ref), atol=1e-6,
                                   rtol=0, err_msg=f"total, step {step}")
        assert set(m_new) == set(m_ref)
        for k in m_ref:
            np.testing.assert_allclose(
                np.asarray(m_new[k]), np.asarray(m_ref[k]), atol=1e-6, rtol=0,
                err_msg=f"{k}, step {step}")
        for k in v_ref:
            np.testing.assert_allclose(
                np.asarray(v_new[k]), np.asarray(v_ref[k]), atol=1e-6, rtol=0,
                err_msg=f"visual {k}, step {step}")
        state, _ = trainer.train_step(state, batch)


def test_fused_matches_reference_example_weight(tiny_setup):
    """Same equivalence for the padded-eval aggregation: a 0-weight example
    (whose values must be excluded exactly) and a non-uniform weight."""
    trainer, state, batch = tiny_setup
    mpi_list, disparity_all = _forward_at(trainer, state, batch)
    for w in ([1.0, 0.0], [2.0, 1.0]):
        ew = jnp.asarray(w, jnp.float32)
        t_new, m_new, _ = loss_mod.compute_losses(
            mpi_list, disparity_all, batch, trainer.cfg, example_weight=ew)
        t_ref, m_ref, _ = _ref_compute_losses(
            mpi_list, disparity_all, batch, trainer.cfg, example_weight=ew)
        np.testing.assert_allclose(float(t_new), float(t_ref), atol=1e-6,
                                   rtol=0, err_msg=f"weights {w}")
        for k in m_ref:
            np.testing.assert_allclose(
                np.asarray(m_new[k]), np.asarray(m_ref[k]), atol=1e-6, rtol=0,
                err_msg=f"{k}, weights {w}")


# ---------------------------------------------------------------------------
# scale plan: cascade + stacked ssim building blocks
# ---------------------------------------------------------------------------

def test_pyramid_cascade_bitwise(tiny_setup):
    """Each cascade level (strided from the level above) must hold exactly
    the elements of striding full-res — stride composition from index 0 —
    and the hoisted intrinsics must equal the old per-scale calls."""
    trainer, _, batch = tiny_setup
    plan = loss_mod.build_scale_plan(batch, trainer.cfg)
    src_full = loss_mod.nchw(batch["src_img"])
    tgt_full = loss_mod.nchw(batch["tgt_img"])
    for s in range(4):
        f = 2 ** s
        assert np.array_equal(np.asarray(plan[s].src_imgs),
                              np.asarray(src_full[:, :, ::f, ::f]))
        assert np.array_equal(np.asarray(plan[s].tgt_imgs),
                              np.asarray(tgt_full[:, :, ::f, ::f]))
        assert np.array_equal(
            np.asarray(plan[s].K_src),
            np.asarray(geometry.scale_intrinsics(batch["K_src"], s)))
        assert np.array_equal(
            np.asarray(plan[s].K_tgt),
            np.asarray(geometry.scale_intrinsics(batch["K_tgt"], s)))
    # lambda gating: v1/v2 active in tiny_setup -> all mask fields populated
    assert plan[0].tgt_edge_masks is not None
    assert plan[0].src_img_grads is not None


def test_scale_plan_lambda_gating(tiny_setup):
    """Zero-lambda configs must not trace the dead mask/grad subgraphs."""
    trainer, _, batch = tiny_setup
    cfg = dataclasses.replace(trainer.cfg, smoothness_lambda_v1=0.0,
                              smoothness_lambda_v2=0.0)
    plan = loss_mod.build_scale_plan(batch, cfg)
    for s in range(4):
        assert plan[s].src_edge_masks is not None  # always-logged src term
        assert plan[s].tgt_edge_masks is None
        assert plan[s].src_img_grads is None
        assert plan[s].tgt_img_grads is None


def test_ssim_pairs_matches_separate_calls():
    """Stacking pairs along the blur batch axis is bitwise exact."""
    rng = np.random.RandomState(7)
    a, b, c, d = (jnp.asarray(rng.rand(2, 3, 24, 40).astype(np.float32))
                  for _ in range(4))
    both = ssim_pairs(jnp.stack([a, c]), jnp.stack([b, d]),
                      size_average=False)
    assert both.shape == (2, 2)
    np.testing.assert_array_equal(
        np.asarray(both[0]), np.asarray(ssim(a, b, size_average=False)))
    np.testing.assert_array_equal(
        np.asarray(both[1]), np.asarray(ssim(c, d, size_average=False)))


# ---------------------------------------------------------------------------
# the dispatch-count acceptance criterion
# ---------------------------------------------------------------------------

def test_blur_einsum_count_drops_4x(tiny_setup):
    """ISSUE acceptance: blur-einsum count in the jitted loss jaxpr drops
    >=4x. The fused pass runs 2 Toeplitz einsums per scale (8 total) where
    the per-scale reference ran 2 ssim calls x 5 operands x 2 einsums = 20
    per scale (80 total) — a 10x drop. The counts are budget entries in
    tools/analysis_baseline.json (ONE source of truth, shared with the
    dot_budget audit pass) and counted by the shared analysis helper."""
    from mine_tpu.analysis.flops import count_blur_dots
    from mine_tpu.analysis.framework import load_baseline

    trainer, _, batch = tiny_setup
    cfg = trainer.cfg
    B, S = 2, 4
    mpi_list = [jnp.zeros((B, S, 4, 64 // 2**s, 64 // 2**s), jnp.float32)
                for s in range(4)]
    disparity = jnp.tile(jnp.linspace(1.0, 0.2, S)[None], (B, 1))

    fused = jax.make_jaxpr(
        lambda m, d, bt: loss_mod.compute_losses(m, d, bt, cfg)[0])(
            mpi_list, disparity, batch)
    ref = jax.make_jaxpr(
        lambda m, d, bt: _ref_compute_losses(m, d, bt, cfg)[0])(
            mpi_list, disparity, batch)

    budgets = load_baseline()["budgets"]
    n_fused = count_blur_dots(fused)
    n_ref = count_blur_dots(ref)
    assert n_fused == budgets["fused_loss.blur_dots"], n_fused
    assert n_ref == budgets["fused_loss.blur_dots_reference"], n_ref
    assert n_fused * 4 <= n_ref


# ---------------------------------------------------------------------------
# dtype audit tool
# ---------------------------------------------------------------------------

_SYNTH_HLO = """
module @jit_train_step {
  func.func public @main() {
    %0 = stablehlo.convert %a : (tensor<2x64x96x256xbf16>) -> tensor<2x64x96x256xf32> loc(#loc1)
    %1 = stablehlo.convert %b : (tensor<128xbf16>) -> tensor<128xf32> loc(#loc2)
    %2 = stablehlo.convert %c : (tensor<4x4xf32>) -> tensor<4x4xf64> loc(#loc1)
    %3 = stablehlo.convert %d : (tensor<bf16>) -> tensor<f32> loc(#loc3)
  }
}
#loc1 = loc("jit(step)/encoder/resnet/conv1/convert_element_type"(#loc9))
#loc2 = loc("jit(step)/batch_norm/convert_element_type"(#loc9))
#loc3 = loc(#loc2)
"""


def test_dtype_audit_collect_and_classify():
    ups = dtype_audit.collect_upcasts(_SYNTH_HLO)
    # the f32->f64 convert is NOT a bf16->f32 upcast
    assert len(ups) == 3
    by_scope = {u["scope"]: u for u in ups}  # jit(...)/ prefixes stripped
    conv = by_scope["encoder/resnet/conv1/convert_element_type"]
    assert conv["elements"] == 2 * 64 * 96 * 256
    assert dtype_audit.in_conv_stack(conv["scope"])
    bn = by_scope["batch_norm/convert_element_type"]
    assert not dtype_audit.in_conv_stack(bn["scope"])
    # loc alias (#loc3 -> #loc2) resolves to the same scope, scalar shape
    scalars = [u for u in ups if u["shape"] == "scalar"]
    assert len(scalars) == 1 and u"batch_norm" in scalars[0]["scope"]

    report = dtype_audit.summarize(ups)
    assert "3 converts" in report
    assert "CONV-STACK SUSPECTS" in report  # the conv1 upcast is unjustified
    assert "f32 BN statistics" in report    # the bn one is annotated


def test_dtype_audit_runs_on_train_step(tiny_setup):
    """ISSUE acceptance: the audit runs on the real jitted train_step. The
    f32 tiny trainer must produce a clean (or justified-only) conv-stack
    report — there is no bf16 to widen."""
    trainer, state, batch = tiny_setup
    ups = dtype_audit.audit_trainer(trainer, state, batch)
    suspects = [u for u in ups if dtype_audit.in_conv_stack(u["scope"])
                and not dtype_audit._justification(u["scope"])]
    assert suspects == [], suspects
    report = dtype_audit.summarize(ups)
    assert ("no bf16->f32 converts" in report) or ("conv-stack: clean" in report)
