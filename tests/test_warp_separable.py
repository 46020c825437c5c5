"""ops/warp_separable.py + kernels/warp_sep.py: the separable warp backend.

Encodes the module docstring's exactness criterion tier by tier:
integer translations BITWISE vs the gather; fractional translations within
~1 ulp (the tent form's 1-(1-t) upper weight vs the gather's direct t);
general in-domain poses within the sep_err * L_y separability bound;
out-of-domain poses bitwise the gather via the lax.cond fallback (compared
jitted-vs-jitted — XLA's eager lerp differs from its jitted lerp by ~1 ulp,
which a bitwise gate must not conflate with the backend under test).

Also gates the two tentpole claims: the traced jaxpr's dot_general FLOPs
drop >=(2*band/W)x vs xla_banded at the flagship shape, and the guard
domain is strictly wider (a pose the 2D banded guard rejects stays on the
separable fast path).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mine_tpu.ops import warp_banded, warp_separable
from mine_tpu.ops.warp import bilinear_sample, homography_warp
from tests import kernel_test_utils


def _grid(B, H_t, W_t):
    yy, xx = jnp.meshgrid(jnp.arange(H_t, dtype=jnp.float32),
                          jnp.arange(W_t, dtype=jnp.float32), indexing="ij")
    return (jnp.broadcast_to(xx, (B, H_t, W_t)),
            jnp.broadcast_to(yy, (B, H_t, W_t)))


def _src(B=2, C=3, H=32, W=40, seed=0):
    return jax.random.uniform(jax.random.PRNGKey(seed), (B, C, H, W))


def test_integer_translation_bitwise():
    """Tier 1: integer translations — anchor exact, tent weights exactly
    {0, 1}, zero-weight terms exact additive identities -> bitwise."""
    src = _src()
    xx, yy = _grid(2, 16, 24)
    cx, cy = xx + 3.0, yy + 2.0
    ref = bilinear_sample(src, cx, cy)
    out = warp_separable.separable_bilinear_sample(src, cx, cy, band=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_fractional_translation_one_ulp():
    """Tier 2: fractional translations — 1-(1-t) double rounding + y-then-x
    vs x-then-y association, ~1 ulp on [0,1)-valued sources."""
    src = _src()
    xx, yy = _grid(2, 16, 24)
    for dx, dy in ((3.7, 2.0), (3.0, 2.3), (3.7, 2.3)):
        ref = bilinear_sample(src, xx + dx, yy + dy)
        out = warp_separable.separable_bilinear_sample(src, xx + dx, yy + dy,
                                                       band=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0, atol=2.5e-7)


def test_general_pose_within_sep_err_bound():
    """Tier 3: sheared pose inside the guard — the value error must respect
    the documented bound sep_err * L_y (vertical Lipschitz constant)."""
    src = _src()
    B, C, H, W = src.shape
    xx, yy = _grid(B, 16, 24)
    cx = xx + 1.7 + 0.03 * yy
    cy = yy + 2.3 + 0.02 * xx          # within-row variation 0.02*23 = 0.46
    ok = warp_separable.guard_ok(src.shape, cy, band=16, sep_tol=0.5)
    assert bool(ok)
    yc = jnp.clip(cy, 0.0, H - 1.0)
    _, sep_err = warp_separable.row_anchor(yc)
    L_y = float(jnp.max(jnp.abs(src[:, :, 1:, :] - src[:, :, :-1, :])))
    ref = bilinear_sample(src, cx, cy)
    out = warp_separable.separable_bilinear_sample(src, cx, cy, band=16)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err <= float(sep_err) * L_y + 1e-5, (err, float(sep_err), L_y)


def test_guard_domain_wider_than_banded():
    """The tentpole's guard claim: within-row variation inflates the 2D
    joint-span band requirement but NOT the separable anchor-span one. This
    pose overflows a band=10 for warp_banded (block span 7 + within-row 4
    + 2 support > 10) yet stays separable-fast (anchor span 7 + 2 <= 10),
    with the approximation still inside the documented bound."""
    src = _src(H=32, W=32)
    xx, yy = _grid(2, 32, 32)
    cx = xx * 1.0
    cy = yy + 4.0 * xx / 31.0           # anchor drift 2.0 per row, span 4
    assert not bool(warp_banded.guard_ok(src.shape, cy, band=10))
    assert bool(warp_separable.guard_ok(src.shape, cy, band=10, sep_tol=2.5))
    _, sep_err = warp_separable.row_anchor(jnp.clip(cy, 0.0, 31.0))
    L_y = float(jnp.max(jnp.abs(src[:, :, 1:, :] - src[:, :, :-1, :])))
    ref = bilinear_sample(src, cx, cy)
    out = warp_separable.separable_bilinear_sample_guarded(
        src, cx, cy, band=10, sep_tol=2.5)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err <= float(sep_err) * L_y + 1e-5, (err, float(sep_err), L_y)


def test_guarded_fallback_bitwise_under_jit():
    """Tier 4: a transpose-like field blows both guard conditions; the cond
    fallback IS bilinear_sample, so jitted output is bitwise the jitted
    gather."""
    src = _src(B=1, C=2, H=16, W=16)
    xx, yy = _grid(1, 16, 16)
    cx, cy = yy, xx                     # 90-degree-style swap
    assert not bool(warp_separable.guard_ok(src.shape, cy, band=4))
    ref = jax.jit(bilinear_sample)(src, cx, cy)
    out = jax.jit(lambda s, x, y: warp_separable.separable_bilinear_sample_guarded(
        s, x, y, band=4))(src, cx, cy)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_grad_matches_gather():
    """Training-readiness gate: plain autodiff through the two einsum
    passes vs the gather's grad (same gate as ops/warp_banded.py)."""
    src = _src(B=2, C=4, H=16, W=24)
    xx, yy = _grid(2, 16, 24)
    cx, cy = xx + 1.7, yy + 2.3

    def loss(fn, s):
        return jnp.sum(fn(s, cx, cy) ** 2)

    g_ref = jax.grad(lambda s: loss(bilinear_sample, s))(src)
    g_out = jax.grad(lambda s: loss(
        lambda s_, x, y: warp_separable.separable_bilinear_sample(
            s_, x, y, band=16), s))(src)
    np.testing.assert_allclose(np.asarray(g_out), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)


def test_bf16_mxu_dtype():
    """bf16 contraction: weights AND the y-resampled intermediate round at
    ~2^-8 relative — one more rounding than the 2D banded path, values in
    [0,1] keep the absolute error well under 2e-2."""
    src = _src()
    xx, yy = _grid(2, 16, 24)
    cx, cy = xx + 3.7, yy + 2.3
    ref = bilinear_sample(src, cx, cy)
    out = warp_separable.separable_bilinear_sample(src, cx, cy, band=16,
                                                   mxu_dtype=jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=2e-2)


def test_homography_warp_separable_path():
    """End-to-end through homography_warp(impl='separable') vs 'xla'."""
    from mine_tpu import geometry
    B, C, H, W = 4, 7, 32, 32
    src = jax.random.uniform(jax.random.PRNGKey(4), (B, C, H, W))
    d = jnp.linspace(1.0, 8.0, B)
    G = jnp.eye(4)[None].repeat(B, 0).at[:, 0, 3].set(0.05)
    K = jnp.asarray(geometry.intrinsics_from_fov(H, W, 60.0))[None].repeat(B, 0)
    K_inv = geometry.inverse_intrinsics(K)
    grid = geometry.cached_pixel_grid(H, W)
    ref, vref = homography_warp(src, d, G, K_inv, K, grid, impl="xla")
    out, vout = homography_warp(src, d, G, K_inv, K, grid, impl="separable",
                                band=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(vout), np.asarray(vref))


def test_trainer_accepts_separable():
    """Config plumbing: one tiny train step with the separable backend."""
    import os

    from mine_tpu.config import CONFIG_DIR, load_config
    from mine_tpu.data.synthetic import make_batch
    from mine_tpu.train.step import SynthesisTrainer
    config = load_config(os.path.join(CONFIG_DIR, "params_llff.yaml"))
    config.update({"data.img_h": 32, "data.img_w": 32,
                   "mpi.num_bins_coarse": 4, "model.num_layers": 18,
                   "training.dtype": "float32",
                   "data.per_gpu_batch_size": 1,
                   "training.warp_backend": "separable",
                   "training.warp_sep_tol": 1.0})
    trainer = SynthesisTrainer(config, steps_per_epoch=10)
    state = trainer.init_state(batch_size=1)
    batch = {k: jnp.asarray(v) for k, v in
             make_batch(1, 32, 32, num_points=32).items()}
    state, metrics = trainer.train_step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["warp_fallback_frac"]))


# ---------------------------------------------------------------------------
# Pallas pair (kernels/warp_sep.py) — interpret mode on CPU, real kernels
# with JAX_PLATFORMS=tpu on a chip (tests/kernel_test_utils.py)
# ---------------------------------------------------------------------------


def test_pallas_fwd_matches_gather():
    from mine_tpu.kernels.warp_sep import pallas_sep_bilinear_sample
    src = _src()
    xx, yy = _grid(2, 16, 24)
    cx, cy = xx + 3.7, yy + 2.3
    ref = bilinear_sample(src, cx, cy)
    out = pallas_sep_bilinear_sample(src, cx, cy, band=16,
                                     interpret=kernel_test_utils.interpret())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=2.5e-7)


def test_pallas_grad_matches_gather():
    """The transposed-splat backward must be the adjoint of the anchored
    forward — gate it against the gather's autodiff grad."""
    from mine_tpu.kernels.warp_sep import separable_sample_diff
    src = _src(B=2, C=4, H=16, W=24)
    xx, yy = _grid(2, 16, 24)
    cx, cy = xx + 1.7, yy + 2.3

    def loss(fn, s):
        return jnp.sum(fn(s, cx, cy) ** 2)

    g_ref = jax.grad(lambda s: loss(bilinear_sample, s))(src)
    g_out = jax.grad(lambda s: loss(
        lambda s_, x, y: separable_sample_diff(
            s_, x, y, 16, 8, kernel_test_utils.interpret()), s))(src)
    np.testing.assert_allclose(np.asarray(g_out), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)


def test_pallas_guarded_fallback_bitwise_under_jit():
    from mine_tpu.kernels.warp_sep import (guard_ok,
                                           separable_sample_diff_guarded)
    src = _src(B=1, C=2, H=16, W=16)
    xx, yy = _grid(1, 16, 16)
    cx, cy = yy, xx
    assert not bool(guard_ok(src.shape, cy, band=4))
    ref = jax.jit(bilinear_sample)(src, cx, cy)
    out = jax.jit(lambda s, x, y: separable_sample_diff_guarded(
        s, x, y, 4, 8, kernel_test_utils.interpret()))(src, cx, cy)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_homography_warp_pallas_sep_path():
    """End-to-end through homography_warp(impl='pallas_sep') vs 'xla'."""
    from mine_tpu import geometry
    B, C, H, W = 4, 7, 32, 32
    src = jax.random.uniform(jax.random.PRNGKey(4), (B, C, H, W))
    d = jnp.linspace(1.0, 8.0, B)
    G = jnp.eye(4)[None].repeat(B, 0).at[:, 0, 3].set(0.05)
    K = jnp.asarray(geometry.intrinsics_from_fov(H, W, 60.0))[None].repeat(B, 0)
    K_inv = geometry.inverse_intrinsics(K)
    grid = geometry.cached_pixel_grid(H, W)
    ref, vref = homography_warp(src, d, G, K_inv, K, grid, impl="xla")
    out, vout = homography_warp(src, d, G, K_inv, K, grid, impl="pallas_sep",
                                band=24)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(vout), np.asarray(vref))


# ---------------------------------------------------------------------------
# The tentpole's FLOP claim, gated on the traced jaxpr
# ---------------------------------------------------------------------------


def test_flop_reduction_vs_banded_at_flagship_shape():
    """ISSUE acceptance: dot_general FLOPs in the traced jaxpr drop
    >=(2*band/W)x vs xla_banded at the flagship LLFF shape (B'=4*32=128,
    C=7, 256x384, band=48). The separable per-row cost 2*C*W*(band+W) vs
    the 2D band's 2*C*band*W*W is a (band+W)/(band*W) ~ 0.023x ratio —
    an order of magnitude under the gate. Counting uses the shared
    analysis helper; the ratio gate is a budget entry in
    tools/analysis_baseline.json (2*48/384 = 0.25), shared with the
    dot_budget audit pass."""
    from mine_tpu.analysis.flops import dot_flops
    from mine_tpu.analysis.framework import load_baseline

    Bp, C, H, W, band = 128, 7, 256, 384, 48
    src = jax.ShapeDtypeStruct((Bp, C, H, W), jnp.float32)
    coords = jax.ShapeDtypeStruct((Bp, H, W), jnp.float32)

    def banded(s, x, y):
        return warp_banded.banded_bilinear_sample(s, x, y, band=band)

    def separable(s, x, y):
        return warp_separable.separable_bilinear_sample(s, x, y, band=band)

    flops_banded = dot_flops(
        jax.make_jaxpr(banded)(src, coords, coords).jaxpr)
    flops_sep = dot_flops(
        jax.make_jaxpr(separable)(src, coords, coords).jaxpr)
    assert flops_banded > 0 and flops_sep > 0
    ratio = load_baseline()["budgets"][
        "warp.separable_vs_banded_max_flop_ratio"]
    assert ratio == 2.0 * band / W  # the budget documents this shape
    bound = flops_banded * ratio
    assert flops_sep <= bound, (flops_sep, flops_banded, flops_sep / flops_banded)
