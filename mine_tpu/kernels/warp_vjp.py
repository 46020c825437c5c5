"""Differentiable banded warp: Pallas forward AND Pallas backward.

Makes the banded bilinear-gather kernel (kernels.warp) usable in the
TRAINING path, replacing the vmapped per-pixel gather (ops/warp.py
bilinear_sample) whose scatter/gather lowering is the worst-case TPU memory
pattern for the reference's hot warp op (homography_sampler.py:138 over a
B*S x 7 x H x W volume, called from mpi_rendering.py:214). Measured on v5e
(round 4): the gather/scatter fusions were 95% of the train step — 0.595
img/s vs 7.99 with these kernels.

Backward = the TRANSPOSED forward (round-4 redesign): the adjoint of
bilinear sampling is bilinear *splatting* with the same coordinates —

  d_src[c,h,w] = sum_{r,wt} g[c,r,wt] * wy(h; sy[r,wt]) * wx(w; sx[r,wt]),
  wy(h; s) = max(1 - |h - s|, 0)   (tent), wx likewise

— and the splat kernel walks the SAME (target-row-block) grid as the
forward, with the same band placement: per block it forms the band-local
outer products A_r = g_r * wy_r and contracts them against the transposed
tent weights on the MXU, accumulating into a full-height d_src block that
stays resident in VMEM across row-blocks (zeroed at the first, written
back once). This replaces the earlier source-block design whose gradient
band ("oband") had to cover the worst target-row touch span — 54+ rows
under vertical compression, 16x the forward's per-block tent work, and a
step-dominating VPU cost. The transposed form does exactly the forward's
tent work, needs no oband concept, no manual DMA, and no lane padding
(all operands are static VMEM blocks).

Because the backward mirrors the forward's band placement row-for-row, it
is the EXACT adjoint of the actual (band-clamped) forward everywhere —
in-domain it equals jax.grad of the ideal gather (test-gated), and the
domain guard is just the forward's (fwd_domain_ok).

Gradients flow to `src` only. The homography coordinates are non-learnable
in MINE training: they derive from sampled disparities, dataset poses, and
the no-grad homography inverse (homography_sampler.py:112-113; the
scale-factor pose edit is also no-grad, synthesis_task.py:441-442), and the
caller (ops/warp.homography_warp) stop-gradients them. The VJP therefore
returns zero cotangents for coords, and a test gates this against jax.grad
of the gather path (tests/test_warp_vjp.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (API parity)

from mine_tpu.kernels.warp import (SUBLANE_ALIGN, band_start, fwd_domain_ok,
                                   mosaic_band_geometry,
                                   pallas_bilinear_sample)


def _bwd_splat_kernel(C: int, BAND: int, RT: int, TW: int,
                      mxu_dtype, y0_ref, g_ref, xc_ref, yc_ref, out_ref):
    """Grid step (b, W_s-tile, target-row-block): splat the block's RT
    gradient rows into its source band; d_src accumulates in the revisited
    full-height output block (W_s-tiled when wide). The row-block dim is
    INNERMOST so each (b, w) output block's revisits are consecutive — a
    non-innermost reduction dim would flush the partial block between
    revisits and corrupt the accumulation (review catch, round 4)."""
    W_t = xc_ref.shape[2]
    # bf16 matmul operands compile only at lane-aligned output widths
    # (Mosaic "Bad lhs type" on silicon); f32 fallback elsewhere — free,
    # the kernels are VPU-bound
    if TW % 128:
        mxu_dtype = jnp.float32
    nb = pl.program_id(2)
    y0 = pl.multiple_of(y0_ref[pl.program_id(0), nb], SUBLANE_ALIGN)
    x_off = (pl.program_id(1) * TW).astype(jnp.float32)

    @pl.when(nb == 0)
    def _zero():
        out_ref[0] = jnp.zeros_like(out_ref[0])

    # source-x positions of this W_s tile along lanes; band row index
    ws = jax.lax.broadcasted_iota(jnp.int32, (W_t, TW), 1).astype(
        jnp.float32) + x_off
    ys = jax.lax.broadcasted_iota(jnp.int32, (BAND, W_t), 0).astype(
        jnp.float32)

    acc = jnp.zeros((C * BAND, TW), jnp.float32)
    for r in range(RT):
        sx = xc_ref[0, r:r + 1, :]                      # [1, W_t]
        sy = yc_ref[0, r:r + 1, :] - y0.astype(jnp.float32)
        sy = jnp.clip(sy, 0.0, BAND - 1.0)  # mirror the fwd coverage clamp
        wy = jnp.maximum(1.0 - jnp.abs(ys - sy), 0.0)   # [BAND, W_t]
        g_r = g_ref[0, :, r, :]                         # [C, W_t]
        A = g_r[:, None, :] * wy[None]                  # [C, BAND, W_t]
        wxT = jnp.maximum(1.0 - jnp.abs(ws - sx.T), 0.0)  # [W_t, TW]
        acc = acc + jnp.dot(
            A.reshape(C * BAND, W_t).astype(mxu_dtype),
            wxT.astype(mxu_dtype), preferred_element_type=jnp.float32)

    cur = out_ref[0, :, pl.ds(y0, BAND), :]             # [C, BAND, TW]
    out_ref[0, :, pl.ds(y0, BAND), :] = cur + acc.reshape(C, BAND, TW)


def _pick_out_tile_w(C: int, H_pad: int, W_s: int,
                     budget: int = 4 * 1024 * 1024) -> int:
    """Largest lane-aligned divisor of W_s keeping the resident d_src
    block under budget (whole width when W_s has no 128-multiple divisor —
    small test shapes only)."""
    if C * H_pad * W_s * 4 <= budget or W_s % 128:
        return W_s
    legal = [d for d in range(128, W_s + 1, 128) if W_s % d == 0]
    fit = [d for d in legal if C * H_pad * d * 4 <= budget]
    return max(fit) if fit else min(legal)


@functools.partial(jax.jit, static_argnames=("src_shape", "band",
                                             "rows_per_block", "interpret",
                                             "mxu_dtype"))
def _warp_bwd(g, coords_x, coords_y, src_shape,
              band: int, rows_per_block: int, interpret: bool,
              mxu_dtype=jnp.float32):
    Bp, C, H_s, W_s = src_shape
    _, H_t, W_t = coords_x.shape
    RT = rows_per_block
    assert H_t % RT == 0, (H_t, RT)
    NB = H_t // RT

    xc = jnp.clip(coords_x, 0.0, W_s - 1.0).astype(jnp.float32)
    yc = jnp.clip(coords_y, 0.0, H_s - 1.0).astype(jnp.float32)

    # EXACTLY the forward's band geometry (kernels/warp.py): ceil band,
    # pad H so the clipped start stays covered, floor-align the starts.
    band = min(band, H_s)
    band, pad_h, _ = mosaic_band_geometry(band, H_s, W_s)
    H_pad = H_s + pad_h
    y0 = band_start(yc, H_pad, band, RT)
    y0 = (y0 // SUBLANE_ALIGN) * SUBLANE_ALIGN

    TW = _pick_out_tile_w(C, H_pad, W_s)
    kernel = functools.partial(_bwd_splat_kernel, C, band, RT, TW,
                               mxu_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(Bp, W_s // TW, NB),  # row-blocks INNERMOST (see kernel doc)
        in_specs=[
            pl.BlockSpec((Bp, NB), lambda b, w, r: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, C, RT, W_t), lambda b, w, r: (b, 0, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, RT, W_t), lambda b, w, r: (b, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, RT, W_t), lambda b, w, r: (b, r, 0),
                         memory_space=pltpu.VMEM),
        ],
        # revisited across row-blocks (r is NOT in the index map): the
        # block stays VMEM-resident per (b, w), zeroed at r==0, written
        # back once — the standard sequential-grid reduction pattern
        out_specs=pl.BlockSpec((1, C, H_pad, TW),
                               lambda b, w, r: (b, 0, 0, w),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Bp, C, H_pad, W_s), jnp.float32),
        name="warp_bilinear_sample_bwd",
        interpret=interpret,
    )(y0, g.astype(jnp.float32), xc, yc)
    return out[:, :, :H_s, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def bilinear_sample_diff(src, coords_x, coords_y,
                         band: int = 48,
                         rows_per_block: int = 8,
                         interpret: bool = False,
                         mxu_dtype=jnp.float32):
    """Differentiable banded bilinear sample: Pallas fwd + Pallas bwd.

    Same contract as ops.warp.bilinear_sample within the band domain (see
    module docstring; use `bilinear_sample_diff_guarded` for unconditional
    correctness). Gradient flows to src; coords receive zeros."""
    return pallas_bilinear_sample(src, coords_x, coords_y, band=band,
                                  rows_per_block=rows_per_block,
                                  interpret=interpret, mxu_dtype=mxu_dtype)


def _diff_fwd(src, coords_x, coords_y, band, rows_per_block,
              interpret, mxu_dtype):
    out = pallas_bilinear_sample(src, coords_x, coords_y, band=band,
                                 rows_per_block=rows_per_block,
                                 interpret=interpret, mxu_dtype=mxu_dtype)
    return out, (src.shape, coords_x, coords_y)


def _diff_bwd(band, rows_per_block, interpret, mxu_dtype, residuals, g):
    src_shape, coords_x, coords_y = residuals
    d_src = _warp_bwd(g, coords_x, coords_y, src_shape=src_shape,
                      band=band, rows_per_block=rows_per_block,
                      interpret=interpret, mxu_dtype=mxu_dtype)
    return d_src, jnp.zeros_like(coords_x), jnp.zeros_like(coords_y)


bilinear_sample_diff.defvjp(_diff_fwd, _diff_bwd)


def diff_domain_ok(src_shape, coords_y, band: int,
                   rows_per_block: int = 8) -> jnp.ndarray:
    """Scalar bool (jit-safe): the banded pair is exact for these coords.

    The transposed backward mirrors the forward's band placement exactly,
    so the domain is just the forward's (span + bilinear support +
    alignment slack fits the band) — the old backward-specific "oband"
    touch-span constraint is gone."""
    _, _, H_s, _ = src_shape
    yc = jnp.clip(coords_y, 0.0, H_s - 1.0).astype(jnp.float32)
    return fwd_domain_ok(yc, H_s, band, rows_per_block)


def guard_ok(src_shape, coords_y, band: int = 48,
             rows_per_block: int = 8) -> jnp.ndarray:
    """THE fallback decision of bilinear_sample_diff_guarded, as a scalar
    bool — exposed so diagnostics (ops/warp.homography_warp's
    with_domain_flag) consume the same logic instead of mirroring it."""
    H_t = coords_y.shape[1]
    if H_t % rows_per_block != 0 or src_shape[2] % rows_per_block != 0:
        return jnp.zeros((), jnp.bool_)
    return diff_domain_ok(src_shape, coords_y, band, rows_per_block)


def bilinear_sample_diff_guarded(src, coords_x, coords_y,
                                 band: int = 48,
                                 rows_per_block: int = 8,
                                 interpret: bool = False,
                                 mxu_dtype=jnp.float32):
    """Banded differentiable warp with a runtime XLA-gather fallback.

    `lax.cond` on the (data-dependent, pose-derived) band-domain check: the
    Pallas fast path for translation-dominated warps, the autodiffed gather
    for rotation-heavy ones. Both branches are differentiable, so this
    composes with jax.grad in the training step. Always returns float32
    (the kernel's accumulation dtype) so the two cond branches agree."""
    from mine_tpu.ops.warp import bilinear_sample

    # the gather fallback honors the same reduced-precision knob as the
    # kernel (mxu_dtype) via the f32-accumulating bf16 gather path, so
    # fallback steps keep the HBM-traffic benefit (parity with
    # ops/warp_banded.py's guard); f32 is a no-op knob
    gather_dtype = mxu_dtype
    src = src.astype(jnp.float32)
    H_t = coords_x.shape[1]
    if H_t % rows_per_block != 0 or src.shape[2] % rows_per_block != 0:
        return bilinear_sample(src, coords_x, coords_y,
                               gather_dtype=gather_dtype)

    ok = guard_ok(src.shape, coords_y, band, rows_per_block)
    return jax.lax.cond(
        ok,
        lambda s, x, y: bilinear_sample_diff(
            s, x, y, band, rows_per_block, interpret, mxu_dtype),
        lambda s, x, y: bilinear_sample(s, x, y, gather_dtype=gather_dtype),
        src, coords_x, coords_y)
