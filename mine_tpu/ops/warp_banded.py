"""Banded one-hot-matmul bilinear warp in pure XLA.

Third implementation of the homography-warp contract (reference hot op:
grid_sample over the B*S x 7 x H x W plane volume, homography_sampler.py:138
called from mpi_rendering.py:214), sitting between the autodiffed gather
(ops/warp.bilinear_sample — worst-case TPU memory pattern) and the Pallas
banded kernel pair (kernels/warp.py + warp_vjp.py — fastest, and the
`auto` backend on a TPU):

  * same banded structure as the Pallas kernel: per block of RT target rows,
    slice a [C, BAND, W_s] source band (translation-dominated homographies
    keep each row-block's source span narrow), then express bilinear
    interpolation as a tent-weight contraction the MXU executes as a matmul
    ([C*BAND, W_s] @ [W_s, W_t] per row) plus a VPU reduction over the band;
  * expressed entirely with lax.scan + lax.dynamic_slice + einsum, so XLA
    differentiates it (dynamic_slice adjoint = padded accumulation — no
    custom VJP needed), it runs on any backend, and the compiler owns
    scheduling/fusion;
  * identical band-coverage semantics to kernels/warp.py: sampling rows are
    clamped into the band, so results match ops.warp.bilinear_sample exactly
    whenever each row-block's source span fits BAND-2 rows (band_span), and
    `banded_bilinear_sample_guarded` falls back to the gather per-call via
    lax.cond outside that domain.

Selected with `training.warp_backend: xla_banded` (the training path; the
video renderer picks between "xla" and the forward-only Pallas kernel by
host-known band checks, infer/video.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from mine_tpu.kernels.warp import band_start, fwd_domain_ok


@functools.partial(jax.jit, static_argnames=("band", "rows_per_block",
                                             "mxu_dtype"))
def banded_bilinear_sample(src: jnp.ndarray,
                           coords_x: jnp.ndarray,
                           coords_y: jnp.ndarray,
                           band: int = 16,
                           rows_per_block: int = 8,
                           mxu_dtype=jnp.float32) -> jnp.ndarray:
    """Banded-matmul equivalent of ops.warp.bilinear_sample (see module
    docstring for the domain requirement).

    Args:
      src: [B', C, H_s, W_s]; coords_x/coords_y: [B', H_t, W_t]
      mxu_dtype: contraction dtype (bfloat16 doubles MXU rate; tent weights
        round at ~2^-8 relative, accumulation stays f32)
    Returns: [B', C, H_t, W_t] float32
    """
    Bp, C, H_s, W_s = src.shape
    _, H_t, W_t = coords_x.shape
    RT = rows_per_block
    assert H_t % RT == 0, (H_t, RT)
    NB = H_t // RT
    band = min(band, H_s)

    src = src.astype(jnp.float32)
    xc = jnp.clip(coords_x, 0.0, W_s - 1.0).astype(jnp.float32)
    yc = jnp.clip(coords_y, 0.0, H_s - 1.0).astype(jnp.float32)

    y0 = band_start(yc, H_s, band, RT)  # [B', NB] — shared placement rule

    xs = jax.lax.broadcasted_iota(jnp.float32, (W_s, W_t), 0)   # src x pos
    ys = jax.lax.broadcasted_iota(jnp.float32, (band, W_t), 0)  # band y pos

    xc_blocks = xc.reshape(Bp, NB, RT, W_t)
    yc_blocks = yc.reshape(Bp, NB, RT, W_t)

    def slice_band(img_chw, y):
        return jax.lax.dynamic_slice(img_chw, (0, y, 0), (C, band, W_s))

    def block_step(_, nb):
        bands = jax.vmap(slice_band)(src, y0[:, nb])      # [B', C, band, W_s]
        bands2 = bands.reshape(Bp, C * band, W_s).astype(mxu_dtype)

        def row_step(__, r):
            sx = xc_blocks[:, nb, r]                             # [B', W_t]
            sy = yc_blocks[:, nb, r] - y0[:, nb, None].astype(jnp.float32)
            sy = jnp.clip(sy, 0.0, band - 1.0)  # band coverage clamp
            # [B', W_s, W_t] one-hot tent weights -> MXU contraction
            wx = jnp.maximum(1.0 - jnp.abs(xs[None] - sx[:, None, :]), 0.0)
            t = jnp.einsum("bks,bst->bkt", bands2, wx.astype(mxu_dtype),
                           preferred_element_type=jnp.float32)
            t = t.reshape(Bp, C, band, W_t)
            wy = jnp.maximum(1.0 - jnp.abs(ys[None] - sy[:, None, :]), 0.0)
            return None, jnp.sum(t * wy[:, None], axis=2)  # [B', C, W_t]

        _, rows = jax.lax.scan(row_step, None, jnp.arange(RT))
        return None, rows  # [RT, B', C, W_t]

    _, blocks = jax.lax.scan(block_step, None, jnp.arange(NB))
    # [NB, RT, B', C, W_t] -> [B', C, NB*RT, W_t]
    return blocks.transpose(2, 3, 0, 1, 4).reshape(Bp, C, H_t, W_t)


def guard_ok(src_shape, coords_y, band: int = 16,
             rows_per_block: int = 8) -> jnp.ndarray:
    """THE fallback decision of banded_bilinear_sample_guarded, as a scalar
    bool — exposed so diagnostics (ops/warp.homography_warp's
    with_domain_flag) consume the same logic instead of mirroring it.

    aligned=False: this path keeps unaligned band starts, so it need not
    budget the Pallas sublane slack — poses within SUBLANE_ALIGN-1 rows of
    the band limit stay on the fast path here (advisor r4)."""
    H_s = src_shape[2]
    H_t = coords_y.shape[1]
    if H_t % rows_per_block != 0:
        return jnp.zeros((), jnp.bool_)
    yc = jnp.clip(coords_y, 0.0, H_s - 1.0)
    return fwd_domain_ok(yc, H_s, band, rows_per_block, aligned=False)


def banded_bilinear_sample_guarded(src, coords_x, coords_y,
                                   band: int = 16,
                                   rows_per_block: int = 8,
                                   mxu_dtype=jnp.float32):
    """Banded XLA warp with the runtime gather fallback.

    Same guard pattern as kernels.warp_vjp.bilinear_sample_diff_guarded:
    lax.cond on the pose-derived band-domain check; both branches are
    XLA-differentiable, so this drops into the training step directly.
    """
    from mine_tpu.ops.warp import bilinear_sample

    # the gather fallback honors the same value dtype (bf16 storage keeps
    # the HBM-traffic benefit when the banded path bails); both paths
    # return f32, so the cond branches agree (f32 is a no-op knob)
    gather_dtype = mxu_dtype

    src = src.astype(jnp.float32)
    H_t = coords_x.shape[1]
    if H_t % rows_per_block != 0:
        return bilinear_sample(src, coords_x, coords_y,
                               gather_dtype=gather_dtype)

    ok = guard_ok(src.shape, coords_y, band, rows_per_block)
    return jax.lax.cond(
        ok,
        lambda s, x, y: banded_bilinear_sample(
            s, x, y, band=band, rows_per_block=rows_per_block,
            mxu_dtype=mxu_dtype),
        lambda s, x, y: bilinear_sample(s, x, y, gather_dtype=gather_dtype),
        src, coords_x, coords_y)
