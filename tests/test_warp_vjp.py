"""Gradient gate for the differentiable banded warp (kernels.warp_vjp):
forward must match the XLA bilinear sampler and the custom-VJP backward must
match jax.grad of the gather path — interpret mode on CPU; the same kernels
compile for TPU (VERDICT round 1 item 3)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mine_tpu.kernels.warp_vjp import (bilinear_sample_diff,
                                       bilinear_sample_diff_guarded,
                                       diff_domain_ok)
from mine_tpu.ops import warp

from tests import kernel_test_utils


def _mild_coords(rng, Bp, H, W):
    """Translation-dominated warp coords (the training regime)."""
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    x = xx[None] + rng.uniform(-4, 4, (Bp, 1, 1)).astype(np.float32) \
        + 0.02 * yy[None]
    y = yy[None] + rng.uniform(-3, 3, (Bp, 1, 1)).astype(np.float32) \
        + 0.03 * xx[None]
    return jnp.asarray(x), jnp.asarray(y)


def _rotation_heavy_coords(rng, Bp, H, W):
    """Steep slope: source-y span per row-block far exceeds any band."""
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    x = xx[None] + 0.0 * yy[None] + np.zeros((Bp, 1, 1), np.float32)
    y = yy[None] + 0.9 * xx[None] + np.zeros((Bp, 1, 1), np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def test_forward_matches_gather():
    rng = np.random.RandomState(0)
    Bp, C, H, W = 2, 7, 32, 48
    src = jnp.asarray(rng.normal(size=(Bp, C, H, W)).astype(np.float32))
    x, y = _mild_coords(rng, Bp, H, W)
    ref = warp.bilinear_sample(src, x, y)
    out = bilinear_sample_diff(src, x, y, 24, 8, kernel_test_utils.interpret())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_grad_matches_gather_path():
    """d(loss)/d(src) of the Pallas pair == jax.grad through the XLA gather."""
    rng = np.random.RandomState(1)
    Bp, C, H, W = 2, 5, 32, 48
    src = jnp.asarray(rng.normal(size=(Bp, C, H, W)).astype(np.float32))
    x, y = _mild_coords(rng, Bp, H, W)
    cot = jnp.asarray(rng.normal(size=(Bp, C, H, W)).astype(np.float32))

    def loss_ref(s):
        return jnp.sum(warp.bilinear_sample(s, x, y) * cot)

    def loss_ker(s):
        return jnp.sum(bilinear_sample_diff(s, x, y, 24, 8, kernel_test_utils.interpret()) * cot)

    g_ref = jax.grad(loss_ref)(src)
    g_ker = jax.grad(loss_ker)(src)
    np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


def test_grad_with_border_clamping():
    """Out-of-image samples: border-clamped weights concentrate gradient on
    edge pixels identically in both paths."""
    rng = np.random.RandomState(2)
    Bp, C, H, W = 1, 3, 16, 32
    src = jnp.asarray(rng.normal(size=(Bp, C, H, W)).astype(np.float32))
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    x = jnp.asarray((xx[None] + rng.uniform(-8, 8, (Bp, H, W))).astype(np.float32))
    y = jnp.asarray((yy[None] + rng.uniform(-2, 2, (Bp, H, W))).astype(np.float32))
    cot = jnp.asarray(rng.normal(size=(Bp, C, H, W)).astype(np.float32))

    g_ref = jax.grad(lambda s: jnp.sum(warp.bilinear_sample(s, x, y) * cot))(src)
    g_ker = jax.grad(lambda s: jnp.sum(
        bilinear_sample_diff(s, x, y, 24, 8, kernel_test_utils.interpret()) * cot))(src)
    np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


def test_domain_check_classifies():
    """Mild coords pass, rotation-heavy fail. Bands of 24 (not 16): the
    guard budgets SUBLANE_ALIGN-1 rows of slack for the Mosaic-mandated
    aligned band starts (kernels/warp.py, round-4 silicon constraint)."""
    rng = np.random.RandomState(3)
    Bp, C, H, W = 2, 3, 32, 48
    shape = (Bp, C, H, W)
    _, y_ok = _mild_coords(rng, Bp, H, W)
    _, y_bad = _rotation_heavy_coords(rng, Bp, H, W)
    assert bool(diff_domain_ok(shape, y_ok, 24, 8))
    assert not bool(diff_domain_ok(shape, y_bad, 24, 8))


def test_guarded_fallback_is_exact():
    """Rotation-heavy coords take the gather branch: value AND grad equal the
    XLA path exactly, so training stays correct for every pose."""
    rng = np.random.RandomState(4)
    Bp, C, H, W = 1, 4, 32, 48
    src = jnp.asarray(rng.normal(size=(Bp, C, H, W)).astype(np.float32))
    x, y = _rotation_heavy_coords(rng, Bp, H, W)
    cot = jnp.asarray(rng.normal(size=(Bp, C, H, W)).astype(np.float32))

    def loss_g(s):
        return jnp.sum(bilinear_sample_diff_guarded(
            s, x, y, band=16, interpret=kernel_test_utils.interpret()) * cot)

    out = bilinear_sample_diff_guarded(src, x, y, band=16,
                                       interpret=kernel_test_utils.interpret())
    ref = warp.bilinear_sample(src, x, y)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(loss_g)(src)
    g_ref = jax.grad(lambda s: jnp.sum(warp.bilinear_sample(s, x, y) * cot))(src)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)


def test_guarded_fast_path_under_jit():
    """In-domain coords inside jit: guarded == gather for value and grad."""
    rng = np.random.RandomState(5)
    Bp, C, H, W = 2, 7, 24, 32
    src = jnp.asarray(rng.normal(size=(Bp, C, H, W)).astype(np.float32))
    x, y = _mild_coords(rng, Bp, H, W)
    cot = jnp.asarray(rng.normal(size=(Bp, C, H, W)).astype(np.float32))

    @jax.jit
    def f(s):
        return jnp.sum(bilinear_sample_diff_guarded(
            s, x, y, band=16, interpret=kernel_test_utils.interpret()) * cot)

    v, g = jax.value_and_grad(f)(src)
    v_ref = jnp.sum(warp.bilinear_sample(src, x, y) * cot)
    g_ref = jax.grad(lambda s: jnp.sum(warp.bilinear_sample(s, x, y) * cot))(src)
    np.testing.assert_allclose(float(v), float(v_ref), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


def test_bf16_mxu_variant_close_to_f32():
    """bfloat16 matmul operands: values and grads within the ~2^-8 tent
    rounding envelope of the f32 path (accumulation stays f32)."""
    import jax.numpy as jnp

    rng = np.random.RandomState(7)
    Bp, C, H, W = 2, 5, 32, 48
    src = jnp.asarray(rng.normal(size=(Bp, C, H, W)).astype(np.float32))
    x, y = _mild_coords(rng, Bp, H, W)
    cot = jnp.asarray(rng.normal(size=(Bp, C, H, W)).astype(np.float32))

    out32 = bilinear_sample_diff(src, x, y, 24, 8, kernel_test_utils.interpret(), jnp.float32)
    out16 = bilinear_sample_diff(src, x, y, 24, 8, kernel_test_utils.interpret(), jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(out16), np.asarray(out32),
                               rtol=0.05, atol=0.03)

    g32 = jax.grad(lambda s: jnp.sum(bilinear_sample_diff(
        s, x, y, 24, 8, kernel_test_utils.interpret(), jnp.float32) * cot))(src)
    g16 = jax.grad(lambda s: jnp.sum(bilinear_sample_diff(
        s, x, y, 24, 8, kernel_test_utils.interpret(), jnp.bfloat16) * cot))(src)
    np.testing.assert_allclose(np.asarray(g16), np.asarray(g32),
                               rtol=0.05, atol=0.05)


def test_coord_cotangents_are_zero():
    """Coords are non-learnable in MINE (module docstring); the VJP must
    return zero cotangents rather than garbage."""
    rng = np.random.RandomState(6)
    Bp, C, H, W = 1, 2, 16, 32
    src = jnp.asarray(rng.normal(size=(Bp, C, H, W)).astype(np.float32))
    x, y = _mild_coords(rng, Bp, H, W)

    gx = jax.grad(lambda xx: jnp.sum(
        bilinear_sample_diff(src, xx, y, 24, 8, kernel_test_utils.interpret())))(x)
    assert float(jnp.max(jnp.abs(gx))) == 0.0


def test_bwd_splat_w_tiled_accumulation(monkeypatch):
    """The d_src block is revisited across row-blocks per (batch, W-tile);
    the reduction is only valid with row-blocks innermost in the grid
    (review catch, round 4). Natural test shapes never tile W (the 4MB
    budget needs W>4k), so force TW < W_s and check grads still match
    jax.grad of the gather exactly."""
    import mine_tpu.kernels.warp_vjp as wv

    monkeypatch.setattr(wv, "_pick_out_tile_w",
                        lambda C, H_pad, W_s, budget=0: 128)
    rng = np.random.RandomState(11)
    Bp, C, H, W = 2, 3, 32, 256  # 2 W-tiles of 128
    src = jnp.asarray(rng.normal(size=(Bp, C, H, W)).astype(np.float32))
    x, y = _mild_coords(rng, Bp, H, W)
    cot = jnp.asarray(rng.normal(size=(Bp, C, H, W)).astype(np.float32))

    g_ref = jax.grad(lambda s: jnp.sum(warp.bilinear_sample(s, x, y) * cot))(src)
    g_ker = jax.grad(lambda s: jnp.sum(wv.bilinear_sample_diff(
        s, x, y, 24, 8, kernel_test_utils.interpret()) * cot))(src)
    np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# windowed contraction (kernels/warp.py subband_plan): the backward splats a
# block's rows of one lane tile into the band rows and source columns their
# taps reach, and the whole band where they do not fit
# ---------------------------------------------------------------------------

def _pair(band):
    return lambda s, x, y: bilinear_sample_diff(
        s, x, y, band, 8, kernel_test_utils.interpret())


# widths 192 and 96: re10k_train's pyramid levels that are no whole lane
# tiles (one tile of the full width, the source padded to 256 / 128)
@pytest.mark.parametrize("hw", [(48, 64), (64, 256), (64, 384), (64, 192),
                                (64, 96)])
def test_windowed_splat_equals_whole_band_bitwise(hw, monkeypatch):
    """In-domain field, every unit windowed: d_src has the same bits as the
    whole-band splat of the same units. Integer cotangents and coordinates
    on a 1/64 grid keep every product and partial sum exact in float32, so
    the comparison does not hang on the order of the additions."""
    from tests.test_warp_kernel import all_units_overflow, sheared_field
    H, W = hw
    rng = np.random.RandomState(21)
    src = jnp.zeros((2, 3, H, W), jnp.float32)
    cot = jnp.asarray(rng.randint(-8, 9, size=(2, 3, H, W)).astype(np.float32))
    x, y = sheared_field(H, W, [1 / 64] * H, shift=(1.75, -2.25))
    x, y = jnp.tile(x, (2, 1, 1)), jnp.tile(y, (2, 1, 1))

    def d_src():
        return np.asarray(jax.grad(
            lambda s: jnp.sum(_pair(48)(s, x, y) * cot))(src))

    windowed = d_src()
    all_units_overflow(monkeypatch)
    whole = d_src()
    jax.clear_caches()
    np.testing.assert_array_equal(windowed, whole)
    ref = jax.grad(lambda s: jnp.sum(warp.bilinear_sample(s, x, y) * cot))(src)
    np.testing.assert_allclose(windowed, np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


def _mixed_case(seed, C=3):
    from tests.test_warp_kernel import mixed_field
    H, W = 64, 256
    rng = np.random.RandomState(seed)
    src = jnp.asarray(rng.normal(size=(1, C, H, W)).astype(np.float32))
    cot = jnp.asarray(rng.normal(size=(1, C, H, W)).astype(np.float32))
    x, y = mixed_field(H, W)
    return src, cot, x, y


def test_mixed_field_forward_and_grad_match_gather():
    """Units that overflow their windows beside units that do not (forward:
    the rows of the upper two blocks; backward: those blocks' lane tiles):
    value and gradient equal the gather's."""
    from mine_tpu.kernels.warp import band_plan
    src, cot, x, y = _mixed_case(22)
    for unit_rows, share in ((1, 0.75), (8, 0.75)):
        fits = band_plan(src.shape, x, y, 64, 8, unit_rows)[-1]
        assert float(jnp.mean(fits.astype(jnp.float32))) == share
    assert bool(diff_domain_ok(src.shape, y, 64, 8))
    out = _pair(64)(src, x, y)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(warp.bilinear_sample(src, x, y)),
                               rtol=1e-4, atol=1e-4)
    g_ker = jax.grad(lambda s: jnp.sum(_pair(64)(s, x, y) * cot))(src)
    g_ref = jax.grad(lambda s: jnp.sum(warp.bilinear_sample(s, x, y) * cot))(src)
    np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


def test_mixed_field_backward_is_the_adjoint():
    """<warp(s), g> = <s, warp^T(g)> with both paths in one call."""
    src, cot, x, y = _mixed_case(23)
    out, vjp = jax.vjp(lambda s: _pair(64)(s, x, y), src)
    d_src, = vjp(cot)
    lhs = float(jnp.sum(out * cot))
    rhs = float(jnp.sum(src * d_src))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-5)


@pytest.mark.parametrize("edge,shift", [("left", (-9.5, 0.0)),
                                        ("right", (9.5, 0.0)),
                                        ("top", (0.0, -5.5)),
                                        ("bottom", (0.0, 5.5))])
def test_grad_border_clamping_through_windows(edge, shift):
    """Border-clamped taps at each edge of the image, every unit windowed:
    the gradient piles up on the edge texels as the gather's does."""
    from mine_tpu.kernels.warp import band_plan
    from tests.test_warp_kernel import sheared_field
    H, W = 64, 256
    rng = np.random.RandomState(24)
    src = jnp.asarray(rng.normal(size=(1, 2, H, W)).astype(np.float32))
    cot = jnp.asarray(rng.normal(size=(1, 2, H, W)).astype(np.float32))
    x, y = sheared_field(H, W, [0.015] * H, shift=shift)
    assert bool(jnp.all(band_plan(src.shape, x, y, 48, 8, 8)[-1]))
    g_ker = jax.grad(lambda s: jnp.sum(_pair(48)(s, x, y) * cot))(src)
    g_ref = jax.grad(lambda s: jnp.sum(warp.bilinear_sample(s, x, y) * cot))(src)
    np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)
