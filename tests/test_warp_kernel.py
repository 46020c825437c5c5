"""Equivalence gate for the Pallas banded warp gather vs the XLA bilinear
sampler (interpret mode on CPU; same kernel compiles for TPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from mine_tpu import geometry
from mine_tpu.kernels.warp import band_span, pallas_bilinear_sample
from mine_tpu.ops import warp

from tests import kernel_test_utils


def test_matches_xla_bilinear_small_motion():
    """Gentle slopes (the video-trajectory regime): must match exactly."""
    rng = np.random.RandomState(0)
    Bp, C, H, W = 3, 7, 32, 64
    src = rng.normal(size=(Bp, C, H, W)).astype(np.float32)
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    # subpixel shifts + mild shear (span per 8-row block << band)
    x = xx[None] + rng.uniform(-3, 3, (Bp, 1, 1)).astype(np.float32) \
        + 0.01 * yy[None]
    y = yy[None] + rng.uniform(-2, 2, (Bp, 1, 1)).astype(np.float32) \
        + 0.02 * xx[None]

    ref = warp.bilinear_sample(jnp.asarray(src), jnp.asarray(x), jnp.asarray(y))
    out = pallas_bilinear_sample(jnp.asarray(src), jnp.asarray(x),
                                 jnp.asarray(y), band=16, interpret=kernel_test_utils.interpret())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_border_clamping_matches():
    """Out-of-image coordinates follow grid_sample(border) semantics."""
    rng = np.random.RandomState(1)
    Bp, C, H, W = 1, 2, 16, 32
    src = rng.normal(size=(Bp, C, H, W)).astype(np.float32)
    x = rng.uniform(-6, W + 6, (Bp, H, W)).astype(np.float32)
    y = np.broadcast_to(np.arange(H, dtype=np.float32)[None, :, None],
                        (Bp, H, W)).copy()
    y += rng.uniform(-0.5, 0.5, (Bp, H, W)).astype(np.float32)

    ref = warp.bilinear_sample(jnp.asarray(src), jnp.asarray(x), jnp.asarray(y))
    out = pallas_bilinear_sample(jnp.asarray(src), jnp.asarray(x),
                                 jnp.asarray(y), band=16, interpret=kernel_test_utils.interpret())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_full_homography_warp_equivalence():
    """End-to-end: the same warp the renderer performs, kernel vs XLA."""
    rng = np.random.RandomState(2)
    Bp, C, H, W = 2, 7, 32, 48
    src = rng.normal(size=(Bp, C, H, W)).astype(np.float32)
    K = jnp.asarray([[[30.0, 0, W / 2], [0, 30.0, H / 2], [0, 0, 1]]] * Bp)
    K_inv = geometry.inverse_intrinsics(K)
    G = jnp.stack([jnp.eye(4).at[0, 3].set(0.05 * (i + 1))
                   .at[1, 3].set(-0.03 * i) for i in range(Bp)])
    d = jnp.asarray([2.0, 3.0])
    grid = geometry.cached_pixel_grid(H, W)

    H_ts = geometry.homography_tgt_src(K, K_inv, G, d)
    H_st = geometry.inverse_3x3(H_ts)
    src_homo = jnp.einsum("bij,jn->bin", H_st, jnp.asarray(grid).reshape(3, -1))
    x = (src_homo[:, 0] / src_homo[:, 2]).reshape(Bp, H, W)
    y = (src_homo[:, 1] / src_homo[:, 2]).reshape(Bp, H, W)

    # the span includes the block's own RT-row extent (~RT-1) plus slope;
    # translation-dominant motion stays within band=16 comfortably
    span = float(band_span(y, H))
    assert span + 2 <= 16, span

    ref, _ = warp.homography_warp(jnp.asarray(src), d, G, K_inv, K, grid)
    out = pallas_bilinear_sample(jnp.asarray(src), x, y, band=16,
                                 interpret=kernel_test_utils.interpret())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_band_span_helper():
    H = 64
    y = np.broadcast_to(np.arange(32, dtype=np.float32)[None, :, None],
                        (1, 32, 16)).copy()
    assert float(band_span(jnp.asarray(y), H, rows_per_block=8)) == 7.0
    y2 = y.copy()
    y2[0, 0, 0] = 40.0  # an outlier stretches its block's span (40 - 0)
    assert float(band_span(jnp.asarray(y2), H, rows_per_block=8)) == 40.0


# ---------------------------------------------------------------------------
# windowed contraction: each (output row, lane tile) unit multiplies only the
# sub-band rows and source columns its taps reach (kernels/warp.py)
# ---------------------------------------------------------------------------

def sheared_field(H, W, slopes, shift=(0.0, 0.0), row_scale=1.0):
    """coords [1, H, W]: y = row_scale * row + slope(row) * column, where
    `slopes` is one slope per output row (a lane tile of 128 columns spans
    128 * slope source rows), x = column + row / 64; both shifted."""
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    x = xx + yy / 64 + shift[0]
    y = row_scale * yy + np.asarray(slopes, np.float32)[:, None] * xx \
        + shift[1]
    return jnp.asarray(x[None]), jnp.asarray(y[None])


STEEP = 0.14  # ~18 source rows across a lane tile: more than a sub-band


def mixed_field(H=64, W=256):
    """Rows 0-15: STEEP, every unit overflows its 16-row sub-band (and no
    coordinate reaches the border clip, which would flatten the span).
    Rows 16-63: gentle, every unit fits. A band of 64 = the whole image
    holds both."""
    return sheared_field(H, W, [STEEP] * 16 + [0.01] * (H - 16))


def all_units_overflow(monkeypatch):
    """Send every unit through the whole-band contraction."""
    from mine_tpu.kernels import warp as kw
    import jax
    plan = kw.subband_plan

    def none_fit(*a, **k):
        table, fits = plan(*a, **k)
        U = (table.shape[-1] - 1) // 3
        table = table.at[:, :, 2 * U:3 * U].set(0).at[:, :, 3 * U].set(1)
        return table, jnp.zeros_like(fits)

    monkeypatch.setattr(kw, "subband_plan", none_fit)
    jax.clear_caches()  # the wrappers are jitted: drop their traces


# widths 192 and 96: re10k_train's pyramid levels that are no whole lane
# tiles (one tile of the full width, the source padded to 256 / 128)
@pytest.mark.parametrize("hw", [(48, 64), (64, 256), (64, 384), (64, 192),
                                (64, 96)])
def test_windowed_equals_whole_band_bitwise(hw, monkeypatch):
    """In-domain field, every unit on the windowed path: the same bits as
    the whole-band contraction of the same units (the skipped rows and
    columns carry tent weights of exactly 0). Texels are small integers and
    coordinates multiples of 1/64, so every product and partial sum is exact
    in float32 and the comparison does not hang on the order in which the
    CPU's matmul adds a pixel's two taps (on the MXU the bf16 products are
    exact for any field)."""
    import jax
    from mine_tpu.kernels.warp import subband_frac
    H, W = hw
    rng = np.random.RandomState(3)
    src = jnp.asarray(rng.randint(-8, 9, size=(2, 3, H, W)).astype(np.float32))
    x, y = sheared_field(H, W, [1 / 64] * H, shift=(1.75, -2.25))
    x, y = jnp.tile(x, (2, 1, 1)), jnp.tile(y, (2, 1, 1))
    assert float(subband_frac(src.shape, x, y, 48)) == 1.0
    windowed = np.asarray(pallas_bilinear_sample(
        src, x, y, band=48, interpret=kernel_test_utils.interpret()))
    all_units_overflow(monkeypatch)
    whole = np.asarray(pallas_bilinear_sample(
        src, x, y, band=48, interpret=kernel_test_utils.interpret()))
    jax.clear_caches()
    np.testing.assert_array_equal(windowed, whole)
    ref = warp.bilinear_sample(src, x, y)
    np.testing.assert_allclose(windowed, np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


def test_mixed_field_matches_gather():
    """Units that overflow their sub-band beside units that do not: the
    kernel merges both paths' rows into one output block."""
    from mine_tpu.kernels.warp import fwd_domain_ok, subband_frac
    H, W = 64, 256
    rng = np.random.RandomState(4)
    src = jnp.asarray(rng.normal(size=(1, 3, H, W)).astype(np.float32))
    x, y = mixed_field(H, W)
    assert bool(fwd_domain_ok(jnp.clip(y, 0, H - 1), H, 64))
    assert float(subband_frac(src.shape, x, y, 64)) == 0.75
    out = pallas_bilinear_sample(src, x, y, band=64,
                                 interpret=kernel_test_utils.interpret())
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(warp.bilinear_sample(src, x, y)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("edge,shift", [("left", (-9.5, 0.0)),
                                        ("right", (9.5, 0.0)),
                                        ("top", (0.0, -5.5)),
                                        ("bottom", (0.0, 5.5))])
def test_border_clamping_through_windows(edge, shift):
    """grid_sample(border) at each edge of the image, every unit on the
    windowed path: clipped taps land in the first / last window."""
    from mine_tpu.kernels.warp import subband_frac
    H, W = 64, 256
    rng = np.random.RandomState(5)
    src = jnp.asarray(rng.normal(size=(1, 2, H, W)).astype(np.float32))
    x, y = sheared_field(H, W, [0.015] * H, shift=shift)
    assert float(subband_frac(src.shape, x, y, 48)) == 1.0
    out = pallas_bilinear_sample(src, x, y, band=48,
                                 interpret=kernel_test_utils.interpret())
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(warp.bilinear_sample(src, x, y)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("field,expected", [
    # every unit inside its sub-band
    (dict(slopes=[0.01] * 64), 1.0),
    # none: rows 0.4 apart keep the steep lines clear of the border clip
    (dict(slopes=[STEEP] * 64, row_scale=0.4), 0.0),
    # the upper quarter's rows overflow
    (dict(slopes=[STEEP] * 16 + [0.01] * 48), 0.75),
])
def test_subband_frac_counts_windowed_units(field, expected):
    """warp_subband_frac's source: at the kernel, and as the guarded warp
    reports it (x the in-domain flag)."""
    from mine_tpu.kernels.warp import subband_frac
    from mine_tpu.kernels.warp_vjp import guarded_subband_frac
    H, W = 64, 256
    x, y = sheared_field(H, W, **field)
    shape = (1, 7, H, W)
    assert float(subband_frac(shape, x, y, 64)) == expected
    assert float(guarded_subband_frac(shape, x, y, 64)) == expected
    # a band the blocks do not fit: the call is on the gather, no unit runs
    assert float(guarded_subband_frac(shape, x, y, 16)) == 0.0
