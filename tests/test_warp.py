"""The bilinear sampler must match torch grid_sample(border,
align_corners=False) after the reference's grid normalization
(homography_sampler.py:136-139) — SURVEY.md lists this as hard part #1."""

import jax.numpy as jnp
import numpy as np
import pytest
import torch
import torch.nn.functional as F

from mine_tpu import geometry
from mine_tpu.ops import warp


def torch_reference_sample(src, x, y):
    """Exactly the reference's normalize + grid_sample path."""
    B, C, H, W = src.shape
    gx = (torch.from_numpy(x) + 0.5) / (W * 0.5) - 1
    gy = (torch.from_numpy(y) + 0.5) / (H * 0.5) - 1
    grid = torch.stack([gx, gy], dim=-1)
    out = F.grid_sample(torch.from_numpy(src), grid=grid,
                        padding_mode="border", align_corners=False)
    return out.numpy()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bilinear_sample_matches_torch_grid_sample(seed):
    rng = np.random.RandomState(seed)
    B, C, H, W = 3, 7, 13, 17
    Ho, Wo = 11, 19
    src = rng.normal(size=(B, C, H, W)).astype(np.float32)
    # coords spanning in-bounds, out-of-bounds, and exact-boundary cases
    x = rng.uniform(-4, W + 4, size=(B, Ho, Wo)).astype(np.float32)
    y = rng.uniform(-4, H + 4, size=(B, Ho, Wo)).astype(np.float32)
    x[0, 0, 0] = 0.0
    y[0, 0, 0] = 0.0
    x[0, 0, 1] = W - 1.0
    y[0, 0, 1] = H - 1.0

    ours = np.asarray(warp.bilinear_sample(
        jnp.asarray(src), jnp.asarray(x), jnp.asarray(y)))
    ref = torch_reference_sample(src, x, y)
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)


def _assert_is_the_gather(impl, out, valid, *warp_args):
    """The Pallas implementations against the gather, bitwise: at whole
    source pixels a tent weight is one-hot, and 1.0 * v plus zeros is exact
    in float32 whichever way the taps are summed."""
    if impl == "xla":
        return
    ref, ref_valid = warp.homography_warp(*warp_args, impl="xla")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(ref_valid))


# heights below are whole row blocks of 8, so that the Pallas
# implementations run their kernel and not a fallback
@pytest.mark.parametrize("impl", warp.WARP_IMPLS)
def test_homography_warp_identity(impl):
    """Identity pose + equal intrinsics must reproduce the source exactly."""
    rng = np.random.RandomState(3)
    B, C, H, W = 2, 4, 8, 10
    src = jnp.asarray(rng.normal(size=(B, C, H, W)).astype(np.float32))
    K = jnp.asarray([[[50.0, 0, 5.0], [0, 50.0, 4.0], [0, 0, 1]]] * B)
    G = jnp.tile(jnp.eye(4), (B, 1, 1))
    d = jnp.full((B,), 3.0)
    grid = geometry.pixel_grid_homogeneous(H, W)
    args = (src, d, G, geometry.inverse_intrinsics(K), K, grid)

    out, valid = warp.homography_warp(*args, impl=impl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(src), rtol=1e-4,
                               atol=1e-4)
    assert bool(jnp.all(valid))
    _assert_is_the_gather(impl, out, valid, *args)


@pytest.mark.parametrize("impl", warp.WARP_IMPLS)
def test_homography_warp_integer_translation(impl):
    """Camera shift of exactly fx*tx/d = 2 pixels: warped image is the source
    shifted by 2 pixels, and pixels that sampled outside are invalid."""
    B, C, H, W = 1, 1, 8, 12
    fx, d = 10.0, 5.0
    tx = 1.0  # pixel shift = fx*tx/d = 2
    img = np.zeros((B, C, H, W), dtype=np.float32)
    img[0, 0, :, 4] = 1.0
    K = jnp.asarray([[[fx, 0, W / 2], [0, fx, H / 2], [0, 0, 1]]])
    G = jnp.eye(4)[None].at[0, 0, 3].set(-tx)
    grid = geometry.pixel_grid_homogeneous(H, W)

    args = (jnp.asarray(img), jnp.asarray([d]), G,
            geometry.inverse_intrinsics(K), K, grid)
    out, valid = warp.homography_warp(*args, impl=impl)
    _assert_is_the_gather(impl, out, valid, *args)
    out = np.asarray(out)
    # target pixel x sees source pixel x + 2 -> the column lights up at x=2
    np.testing.assert_allclose(out[0, 0, :, 2], 1.0, atol=1e-5)
    assert np.abs(out[0, 0, :, 4]).max() < 1e-5
    # the rightmost two target columns sample source x in [W, W+2) -> invalid
    v = np.asarray(valid)
    assert not v[0, :, W - 1].any()
    assert v[0, :, : W - 2].all()


@pytest.mark.parametrize("impl", ["xla", "pallas_diff"])
def test_warp_gradients_flow_through_values(impl):
    """Gradients flow through the sampled *values* (the MPI planes produced by
    the network). The warp grid itself is deliberately no-grad, matching the
    reference's no_grad homography inverse (homography_sampler.py:112-113)."""
    import jax

    B, C, H, W = 1, 2, 8, 5
    rng = np.random.RandomState(4)
    src0 = jnp.asarray(rng.normal(size=(B, C, H, W)).astype(np.float32))
    K = jnp.asarray([[[10.0, 0, 2.0], [0, 10.0, 2.0], [0, 0, 1]]])
    grid = geometry.pixel_grid_homogeneous(H, W)
    G = jnp.eye(4)[None].at[0, 0, 3].set(0.13)

    def loss(src):
        out, _ = warp.homography_warp(src, jnp.asarray([2.0]), G,
                                      geometry.inverse_intrinsics(K), K, grid,
                                      impl=impl)
        return jnp.sum(out ** 2)

    g = jax.grad(loss)(src0)
    g = np.asarray(g)
    assert np.all(np.isfinite(g)) and np.abs(g).max() > 0

    def loss_t(t):
        G2 = jnp.eye(4)[None].at[0, 0, 3].set(t)
        out, _ = warp.homography_warp(src0, jnp.asarray([2.0]), G2,
                                      geometry.inverse_intrinsics(K), K, grid,
                                      impl=impl)
        return jnp.sum(out ** 2)

    # pose gradient via the grid is intentionally blocked
    assert float(jax.grad(loss_t)(0.1)) == 0.0


def test_bilinear_sample_bf16_gather_close():
    """gather_dtype=bfloat16 (training.warp_dtype on the gather path) stays
    within bf16 value rounding of the f32 gather."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mine_tpu.ops.warp import bilinear_sample
    B, C, H, W = 2, 7, 24, 32
    src = jax.random.uniform(jax.random.PRNGKey(0), (B, C, H, W))
    cx = jax.random.uniform(jax.random.PRNGKey(1), (B, H, W)) * (W - 1)
    cy = jax.random.uniform(jax.random.PRNGKey(2), (B, H, W)) * (H - 1)
    ref = bilinear_sample(src, cx, cy)
    out = bilinear_sample(src, cx, cy, gather_dtype=jnp.bfloat16)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-2)


def test_bilinear_sample_bf16_backward_accumulates_f32():
    """The bf16-storage gather's backward scatter must accumulate in f32.

    Adversarial case: EVERY target pixel samples the same source texel, so
    d_src at that texel is a sum of Ho*Wo cotangents. A bf16 scatter-add
    stalls once the running sum is ~2^8 times a contribution; the custom-VJP
    f32 scatter must match the f32 path near-exactly (not at bf16 rounding).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mine_tpu.ops.warp import bilinear_sample
    B, C, H, W = 1, 1, 8, 1024
    src = jnp.ones((B, C, H, W), jnp.float32)
    # all coords at exactly texel (2, 3): integer coords, no lerp spread
    cx = jnp.full((B, H, W), 3.0)
    cy = jnp.full((B, H, W), 2.0)

    def loss(s, dt):
        return jnp.sum(bilinear_sample(s, cx, cy, gather_dtype=dt))

    g_ref = jax.grad(loss)(src, None)
    g_bf = jax.grad(loss)(src, jnp.bfloat16)
    assert g_bf.dtype == jnp.float32
    # the hot texel accumulates H*W = 8192 ones; bf16 accumulation would
    # plateau around 256
    assert float(g_ref[0, 0, 2, 3]) == float(H * W)
    np.testing.assert_allclose(np.asarray(g_bf), np.asarray(g_ref), rtol=1e-6)

    # gradient must also match for fractional coords (lerp weights applied)
    cx2 = jnp.full((B, H, W), 3.25)
    cy2 = jnp.full((B, H, W), 2.5)

    def loss2(s, dt):
        return jnp.sum(bilinear_sample(s, cx2, cy2, gather_dtype=dt) ** 2)

    g2_ref = jax.grad(loss2)(src, None)
    g2_bf = jax.grad(loss2)(src, jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(g2_bf), np.asarray(g2_ref),
                               rtol=2e-2)
