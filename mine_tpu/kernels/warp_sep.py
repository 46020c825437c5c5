"""Pallas TPU kernel pair for the SEPARABLE banded warp (fwd + bwd).

The Pallas twin of ops/warp_separable.py — same two-pass factorization
(per-row scalar y anchor, banded y resample, exact per-pixel x resample),
same correctness domain, same sep_err <= sep_tol guard. See that module's
docstring for the math, the error bound, and the exactness criterion; this
file is only about the TPU mapping:

  * forward walks the SAME (batch, target-row-block) grid as
    kernels/warp.py and DMAs the same [C, BAND, W_s] source band per block
    (band placement from the per-row anchors via the shared band_start).
    Per row the y pass is a VPU weighted reduction over the band with ONE
    scalar tent per row (the anchor lives in an SMEM [B', H_t] table —
    scalar-varying weights don't batch into a single MXU op without a
    band transpose), and the x pass is the ONLY MXU contraction:
    [C, W_s] @ [W_s, W_t] per row — vs the 2D kernel's [C*BAND, W_s] @
    [W_s, W_t], the full (2*BAND/W)x-and-better MXU cut of the tentpole;
  * backward is the transposed forward, reusing the kernels/warp_vjp.py
    band machinery verbatim (mosaic_band_geometry, band_start alignment,
    _pick_out_tile_w W-tiling, revisited full-height d_src block with the
    row-block grid dim innermost): per row, gx_r = g_r @ wx^T on the MXU
    ([C, W_t] @ [W_t, TW] — again BANDx smaller than the 2D splat's
    [C*BAND, W_t] lhs), then a VPU splat of gx_r against the row's scalar
    y tent into the band accumulator. Because it mirrors the forward's
    band placement and anchor row-for-row, it is the EXACT adjoint of the
    actual (band-clamped, anchored) forward everywhere.

Gradients flow to src only; coords get zero cotangents (the caller
stop-gradients them — same contract as kernels/warp_vjp.py).

Selected with `training.warp_backend: pallas_sep` (opt-in; `auto` still
resolves to pallas_diff/xla until this variant is chip-measured).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mine_tpu.kernels.warp import (SUBLANE_ALIGN, band_start, fwd_domain_ok,
                                   mosaic_band_geometry)
from mine_tpu.kernels.warp_vjp import _pick_out_tile_w
from mine_tpu.ops.warp_separable import row_anchor


def _sep_fwd_kernel(C: int, BAND: int, RT: int, H_pad: int, W_s: int,
                    mxu_dtype, y0_ref, sy_ref, xc_ref, src_ref, out_ref,
                    band_buf, sem):
    W_t = xc_ref.shape[2]
    # bf16 matmul operands compile only at lane-aligned output widths
    # (Mosaic "Bad lhs type" on silicon, round-4 window); f32 elsewhere
    if W_t % 128:
        mxu_dtype = jnp.float32
    b = pl.program_id(0)
    nb = pl.program_id(1)
    y0 = pl.multiple_of(y0_ref[b, nb], SUBLANE_ALIGN)

    # src stays in HBM (ANY); the anchor-placed band arrives via dynamic DMA
    dma = pltpu.make_async_copy(
        src_ref.at[b, :, pl.ds(y0, BAND), :], band_buf, sem)
    dma.start()
    dma.wait()

    band = band_buf[:]                              # [C, BAND, W_s] f32
    # Mosaic iota must be integer-typed; cast to f32 for the tent weights
    xs = jax.lax.broadcasted_iota(jnp.int32, (W_s, W_t), 0).astype(
        jnp.float32)
    ys = jax.lax.broadcasted_iota(jnp.int32, (BAND, W_s), 0).astype(
        jnp.float32)

    for r in range(RT):
        # band-relative anchor, pre-clipped on the host side (SMEM scalar)
        sy = sy_ref[b, nb * RT + r]
        wy = jnp.maximum(1.0 - jnp.abs(ys - sy), 0.0)   # [BAND, W_s]
        # y pass: VPU band reduction at ONE scalar tent per row
        tmp = jnp.sum(band * wy[None], axis=1)          # [C, W_s]
        sx = xc_ref[0, r:r + 1, :]                      # [1, W_t]
        wx = jnp.maximum(1.0 - jnp.abs(xs - sx), 0.0)   # [W_s, W_t]
        # x pass: the only MXU contraction — [C, W_s] lhs, BANDx smaller
        # than the 2D kernel's [C*BAND, W_s]
        out_ref[0, :, r, :] = jnp.dot(tmp.astype(mxu_dtype),
                                      wx.astype(mxu_dtype),
                                      preferred_element_type=jnp.float32)


def _sep_geometry(coords_y, H_s: int, W_s: int, band: int,
                  rows_per_block: int):
    """Shared fwd/bwd band placement: anchor the band with the per-row
    midrange (ops/warp_separable.row_anchor), apply THE Mosaic alignment
    recipe (mosaic_band_geometry + sublane-floored starts), and pre-bake
    the band-relative clipped anchors for the kernels' SMEM scalar table.

    Returns (band, pad_h, pad_w, y0 [B', NB] i32, sy [B', H_t] f32)."""
    RT = rows_per_block
    yc = jnp.clip(coords_y, 0.0, H_s - 1.0).astype(jnp.float32)
    anchor, _ = row_anchor(yc)                       # [B', H_t]
    band = min(band, H_s)
    band, pad_h, pad_w = mosaic_band_geometry(band, H_s, W_s)
    H_pad = H_s + pad_h
    y0 = band_start(anchor[:, :, None], H_pad, band, RT)
    y0 = (y0 // SUBLANE_ALIGN) * SUBLANE_ALIGN
    y0f = jnp.repeat(y0, RT, axis=1).astype(jnp.float32)  # [B', H_t]
    sy = jnp.clip(anchor - y0f, 0.0, band - 1.0)
    return band, pad_h, pad_w, y0, sy


@functools.partial(jax.jit,
                   static_argnames=("band", "rows_per_block", "interpret",
                                    "mxu_dtype"))
def pallas_sep_bilinear_sample(src: jnp.ndarray,
                               coords_x: jnp.ndarray,
                               coords_y: jnp.ndarray,
                               band: int = 16,
                               rows_per_block: int = 8,
                               interpret: bool = False,
                               mxu_dtype=jnp.float32) -> jnp.ndarray:
    """Separable-banded equivalent of ops.warp.bilinear_sample (forward).

    Args:
      src: [B', C, H_s, W_s]; coords_x/coords_y: [B', H_t, W_t]
      mxu_dtype: x-matmul operand dtype (bfloat16 doubles MXU rate; the
        y-resampled intermediate rounds at ~2^-8 relative, accumulation
        stays f32)
    Returns: [B', C, H_t, W_t] float32
    """
    Bp, C, H_s, W_s = src.shape
    _, H_t, W_t = coords_x.shape
    RT = rows_per_block
    assert H_t % RT == 0, (H_t, RT)
    NB = H_t // RT

    xc = jnp.clip(coords_x, 0.0, W_s - 1.0).astype(jnp.float32)
    band, pad_h, pad_w, y0, sy = _sep_geometry(coords_y, H_s, W_s, band, RT)
    # same padding contract as kernels/warp.py: padded rows/cols sit >= 1
    # beyond the clip range of the (clipped) coords, so their tent weights
    # are exactly zero — numerics unchanged
    if pad_h or pad_w:
        src = jnp.pad(src, ((0, 0), (0, 0), (0, pad_h), (0, pad_w)))
    H_pad, W_sp = src.shape[2], src.shape[3]

    kernel = functools.partial(_sep_fwd_kernel, C, band, RT, H_pad, W_sp,
                               mxu_dtype)
    return pl.pallas_call(
        kernel,
        grid=(Bp, NB),
        in_specs=[
            pl.BlockSpec((Bp, NB), lambda b, r: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((Bp, H_t), lambda b, r: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, RT, W_t), lambda b, r: (b, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Bp, C, H_pad, W_sp), lambda b, r: (0, 0, 0, 0),
                         memory_space=pl.ANY),  # stays in HBM; banded DMA
        ],
        out_specs=pl.BlockSpec((1, C, RT, W_t), lambda b, r: (b, 0, r, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Bp, C, H_t, W_t), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((C, band, W_sp), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
        name="warp_sep_bilinear_sample_fwd",
        interpret=interpret,
    )(y0, sy, xc, src.astype(jnp.float32))


def _sep_bwd_kernel(C: int, BAND: int, RT: int, TW: int,
                    mxu_dtype, y0_ref, sy_ref, g_ref, xc_ref, out_ref):
    """Transposed separable forward (grid (b, W_s-tile, row-block), the
    row-block dim INNERMOST so the revisited full-height d_src block's
    accumulation is never flushed mid-reduction — same pattern and reason
    as kernels/warp_vjp._bwd_splat_kernel)."""
    W_t = xc_ref.shape[2]
    if TW % 128:
        mxu_dtype = jnp.float32
    b = pl.program_id(0)
    nb = pl.program_id(2)
    y0 = pl.multiple_of(y0_ref[b, nb], SUBLANE_ALIGN)
    x_off = (pl.program_id(1) * TW).astype(jnp.float32)

    @pl.when(nb == 0)
    def _zero():
        out_ref[0] = jnp.zeros_like(out_ref[0])

    ws = jax.lax.broadcasted_iota(jnp.int32, (W_t, TW), 1).astype(
        jnp.float32) + x_off
    ys = jax.lax.broadcasted_iota(jnp.int32, (BAND, TW), 0).astype(
        jnp.float32)

    acc = jnp.zeros((C, BAND, TW), jnp.float32)
    for r in range(RT):
        sx = xc_ref[0, r:r + 1, :]                      # [1, W_t]
        wxT = jnp.maximum(1.0 - jnp.abs(ws - sx.T), 0.0)  # [W_t, TW]
        g_r = g_ref[0, :, r, :]                         # [C, W_t]
        # adjoint x pass on the MXU: [C, W_t] lhs vs the 2D splat's
        # [C*BAND, W_t] — the same BANDx operand cut as the forward
        gx = jnp.dot(g_r.astype(mxu_dtype), wxT.astype(mxu_dtype),
                     preferred_element_type=jnp.float32)  # [C, TW]
        sy = sy_ref[b, nb * RT + r]
        wy = jnp.maximum(1.0 - jnp.abs(ys - sy), 0.0)   # [BAND, TW]
        # adjoint y pass: VPU splat of the row gradient along its tent
        acc = acc + gx[:, None, :] * wy[None]

    cur = out_ref[0, :, pl.ds(y0, BAND), :]             # [C, BAND, TW]
    out_ref[0, :, pl.ds(y0, BAND), :] = cur + acc


@functools.partial(jax.jit, static_argnames=("src_shape", "band",
                                             "rows_per_block", "interpret",
                                             "mxu_dtype"))
def _sep_bwd(g, coords_x, coords_y, src_shape,
             band: int, rows_per_block: int, interpret: bool,
             mxu_dtype=jnp.float32):
    Bp, C, H_s, W_s = src_shape
    _, H_t, W_t = coords_x.shape
    RT = rows_per_block
    assert H_t % RT == 0, (H_t, RT)
    NB = H_t // RT

    xc = jnp.clip(coords_x, 0.0, W_s - 1.0).astype(jnp.float32)
    # EXACTLY the forward's anchor + band geometry (shared helper), so the
    # splat lands in the same rows the forward read (no lane padding here:
    # all bwd operands are static VMEM blocks, same as _warp_bwd)
    band, pad_h, _, y0, sy = _sep_geometry(coords_y, H_s, W_s, band, RT)
    H_pad = H_s + pad_h

    TW = _pick_out_tile_w(C, H_pad, W_s)
    kernel = functools.partial(_sep_bwd_kernel, C, band, RT, TW, mxu_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(Bp, W_s // TW, NB),  # row-blocks INNERMOST (see kernel doc)
        in_specs=[
            pl.BlockSpec((Bp, NB), lambda b, w, r: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((Bp, H_t), lambda b, w, r: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, C, RT, W_t), lambda b, w, r: (b, 0, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, RT, W_t), lambda b, w, r: (b, r, 0),
                         memory_space=pltpu.VMEM),
        ],
        # revisited across row-blocks (r not in the index map): VMEM-
        # resident per (b, w), zeroed at r==0, written back once
        out_specs=pl.BlockSpec((1, C, H_pad, TW),
                               lambda b, w, r: (b, 0, 0, w),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Bp, C, H_pad, W_s), jnp.float32),
        name="warp_sep_bilinear_sample_bwd",
        interpret=interpret,
    )(y0, sy, g.astype(jnp.float32), xc)
    return out[:, :, :H_s, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def separable_sample_diff(src, coords_x, coords_y,
                          band: int = 48,
                          rows_per_block: int = 8,
                          interpret: bool = False,
                          mxu_dtype=jnp.float32):
    """Differentiable separable banded sample: Pallas fwd + Pallas bwd.

    Same contract as ops.warp_separable.separable_bilinear_sample within
    the band+separability domain (use `separable_sample_diff_guarded` for
    unconditional correctness). Gradient flows to src; coords get zeros."""
    return pallas_sep_bilinear_sample(src, coords_x, coords_y, band=band,
                                      rows_per_block=rows_per_block,
                                      interpret=interpret,
                                      mxu_dtype=mxu_dtype)


def _sep_diff_fwd(src, coords_x, coords_y, band, rows_per_block,
                  interpret, mxu_dtype):
    out = pallas_sep_bilinear_sample(src, coords_x, coords_y, band=band,
                                     rows_per_block=rows_per_block,
                                     interpret=interpret,
                                     mxu_dtype=mxu_dtype)
    return out, (src.shape, coords_x, coords_y)


def _sep_diff_bwd(band, rows_per_block, interpret, mxu_dtype, residuals, g):
    src_shape, coords_x, coords_y = residuals
    d_src = _sep_bwd(g, coords_x, coords_y, src_shape=src_shape,
                     band=band, rows_per_block=rows_per_block,
                     interpret=interpret, mxu_dtype=mxu_dtype)
    return d_src, jnp.zeros_like(coords_x), jnp.zeros_like(coords_y)


separable_sample_diff.defvjp(_sep_diff_fwd, _sep_diff_bwd)


def sep_domain_ok(src_shape, coords_y, band: int,
                  rows_per_block: int = 8,
                  sep_tol: float = 0.5) -> jnp.ndarray:
    """Scalar bool (jit-safe): the separable Pallas pair is within its
    documented error bound for these coords — the anchors' block span fits
    the band (aligned=True: this path floors band starts to the sublane
    tile, so the alignment slack IS in the budget) AND the anchor
    deviation is <= sep_tol. The transposed backward mirrors the forward's
    placement, so one domain covers both."""
    H_s = src_shape[2]
    yc = jnp.clip(coords_y, 0.0, H_s - 1.0).astype(jnp.float32)
    anchor, sep_err = row_anchor(yc)
    band_fits = fwd_domain_ok(anchor[:, :, None], H_s, band,
                              rows_per_block, aligned=True)
    return band_fits & (sep_err <= sep_tol)


def guard_ok(src_shape, coords_y, band: int = 48,
             rows_per_block: int = 8,
             sep_tol: float = 0.5) -> jnp.ndarray:
    """THE fallback decision of separable_sample_diff_guarded, as a scalar
    bool — exposed so diagnostics (ops/warp.homography_warp's
    with_domain_flag) consume the same logic instead of mirroring it."""
    H_t = coords_y.shape[1]
    if H_t % rows_per_block != 0 or src_shape[2] % rows_per_block != 0:
        return jnp.zeros((), jnp.bool_)
    return sep_domain_ok(src_shape, coords_y, band, rows_per_block, sep_tol)


def separable_sample_diff_guarded(src, coords_x, coords_y,
                                  band: int = 48,
                                  rows_per_block: int = 8,
                                  interpret: bool = False,
                                  mxu_dtype=jnp.float32,
                                  sep_tol: float = 0.5):
    """Separable Pallas warp with a runtime XLA-gather fallback.

    `lax.cond` on the (data-dependent, pose-derived) band+separability
    check: the Pallas fast path for translation-dominated warps, the
    autodiffed gather for rotation-heavy or shear-heavy ones. Both branches
    are differentiable, so this composes with jax.grad in the training
    step. Always returns float32 so the two cond branches agree."""
    from mine_tpu.ops.warp import bilinear_sample

    # fallback honors the same reduced-precision knob (parity with the
    # other guarded backends); f32 is a no-op knob
    gather_dtype = mxu_dtype
    src = src.astype(jnp.float32)
    H_t = coords_x.shape[1]
    if H_t % rows_per_block != 0 or src.shape[2] % rows_per_block != 0:
        return bilinear_sample(src, coords_x, coords_y,
                               gather_dtype=gather_dtype)

    ok = guard_ok(src.shape, coords_y, band, rows_per_block, sep_tol)
    return jax.lax.cond(
        ok,
        lambda s, x, y: separable_sample_diff(
            s, x, y, band, rows_per_block, interpret, mxu_dtype),
        lambda s, x, y: bilinear_sample(s, x, y, gather_dtype=gather_dtype),
        src, coords_x, coords_y)
