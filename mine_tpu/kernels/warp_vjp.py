"""Differentiable banded warp: Pallas forward AND Pallas backward.

Makes the banded bilinear-gather kernel (kernels.warp) usable in the
TRAINING path, replacing the vmapped per-pixel gather (ops/warp.py
bilinear_sample) whose scatter/gather lowering is the worst-case TPU memory
pattern for the reference's hot warp op (homography_sampler.py:138 over a
B*S x 7 x H x W volume, called from mpi_rendering.py:214; B*S x 4 x H x W
here since PR 36, the plane points being a formula and carrying no gradient
to any parameter: ops/rendering.py). Measured on v5e
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
(all operands are static VMEM blocks). Like the forward it multiplies only
what a unit's taps reach (kernels/warp.py, "the band is sized for a
BLOCK"): its unit is the block's 8 rows of one lane tile, splatted into a
sub-band of 24 rows and the forward's column window, the 8 rows summed
inside one accumulation so d_src is read and written once per unit. The
whole-band form was MXU-bound on zeros (27.5 ms at 64x7x384x512, band 48,
v5e); this one takes 10.2 ms (my chip run, PR 29). At the step's four
channels it takes 5.8 ms against 9.5 for seven in llff_train's step (my
chip run, PR 36; 1.22 / 1.83 at 192x256), and its resident d_src block is
3.1 MB at 384x512 where it was 5.5.

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
from jax.experimental.pallas import tpu as pltpu

from mine_tpu.kernels.warp import (LANE_ALIGN, SUBLANE_ALIGN, _f32,
                                   _iota_rows, _lane_tiles, _pick_row, _tent,
                                   band_plan, fwd_domain_ok,
                                   pallas_bilinear_sample, subband_frac,
                                   subband_geometry)


def _bwd_splat_kernel(C: int, BAND: int, SUB: int, RT: int, TW: int,
                      TILE: int, KW: int, mxu_dtype, y0_ref, plan_ref, g_ref,
                      xc_ref, yc_ref, out_ref):
    """Grid step (b, W_s-tile, target-row-block): splat the block's RT
    gradient rows into its source band; d_src accumulates in the revisited
    full-height output block (W_s-tiled when wide). The row-block dim is
    INNERMOST so each (b, w) output block's revisits are consecutive — a
    non-innermost reduction dim would flush the partial block between
    revisits and corrupt the accumulation (review catch, round 4).

    The forward's windows, transposed (kernels/warp.py _warp_kernel), with
    the block's rows of one lane tile as the unit: a unit that fits its
    window splats into SUB band rows and KW source columns only, its RT
    rows summed inside one accumulation and added to d_src once; a unit
    that does not fit is masked out of that pass and splatted into the
    whole band, as before the windowed form existed. Same tent weights,
    zero terms left out: the pair stays adjoint unit by unit."""
    W_t = xc_ref.shape[2]
    T = W_t // TILE
    # bf16 matmul operands compile only at lane-aligned output widths
    # (Mosaic "Bad lhs type" on silicon); f32 fallback elsewhere
    if TW % 128:
        mxu_dtype = jnp.float32
    nb = pl.program_id(2)
    y0 = pl.multiple_of(y0_ref[pl.program_id(0), nb], SUBLANE_ALIGN)
    x_off = pl.program_id(1) * TW
    y0f = y0.astype(jnp.float32)

    @pl.when(nb == 0)
    def _zero():
        out_ref[0] = jnp.zeros_like(out_ref[0])

    iota_rows = functools.partial(_iota_rows, width=TILE)

    def splat(g_r, wy, wx):
        """[C, 1, TILE] gradient row x [n, TILE] row weights on the VPU,
        then x [K, TILE] column weights on the MXU: [C * n, K]. The column
        weights keep the forward's layout (source columns on sublanes,
        target columns on lanes) and the matmul contracts the lane axes of
        both operands: A @ wx^T with no transpose of either."""
        A = (g_r * wy[None]).reshape(C * wy.shape[0], TILE)
        return jax.lax.dot_general(
            A.astype(mxu_dtype), wx.astype(mxu_dtype),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    def band_rel(sy):
        # the forward's coverage clamp, spelled as it is there
        return jnp.minimum(jnp.maximum(sy - y0f, 0.0), BAND - 1.0)

    xs, ys = iota_rows(KW), iota_rows(SUB)

    def windowed(j, lanes):
        # both starts are tile-aligned by subband_plan; multiple_of carries
        # that to Mosaic for the dynamic VMEM slices
        s0 = pl.multiple_of(plan_ref[0, 0, j], SUBLANE_ALIGN) \
            if SUB < BAND else 0
        # this W_s tile's part of the unit's column window
        k0 = pl.multiple_of(
            jnp.clip(plan_ref[0, 0, T + j] - x_off, 0, TW - KW),
            LANE_ALIGN) if KW < TW else 0
        fits = plan_ref[0, 0, 2 * T + j].astype(jnp.float32)
        acc = jnp.zeros((C * SUB, KW), jnp.float32)
        for r in range(RT):
            sx = xc_ref[0, r:r + 1, lanes]                  # [1, TILE]
            sy = band_rel(yc_ref[0, r:r + 1, lanes])
            g_r = g_ref[0, :, r:r + 1, lanes] * fits        # [C, 1, TILE]
            acc = acc + splat(g_r, _tent(ys - (sy - _f32(s0))),
                              _tent(xs - (sx - _f32(k0 + x_off))))
        rows = pl.ds(pl.multiple_of(y0 + s0, SUBLANE_ALIGN), SUB)
        out_ref[0, :, rows, pl.ds(k0, KW)] += acc.reshape(C, SUB, KW)

    _lane_tiles(T, TILE, windowed, unroll=True)

    @pl.when(plan_ref[0, 0, 3 * T] != 0)   # a unit of this block overflows
    def _whole_band():
        # the rare path: loops over rows and tiles keep it small; the row
        # is picked by a masked sum (_pick_row)
        xs_all = iota_rows(TW) + _f32(x_off)
        ys_all = iota_rows(BAND)

        def row_of_unit(r, j, lanes):
            @pl.when(plan_ref[0, 0, 2 * T + j] == 0)
            def _overflows():
                sx = _pick_row(xc_ref[0, :, lanes], r)
                sy = band_rel(_pick_row(yc_ref[0, :, lanes], r))
                d = splat(_pick_row(g_ref[0, :, :, lanes], r),
                          _tent(ys_all - sy), _tent(xs_all - sx))
                out_ref[0, :, pl.ds(y0, BAND), :] += d.reshape(C, BAND, TW)

        def row(r, carry):
            _lane_tiles(T, TILE, functools.partial(row_of_unit, r))
            return carry

        jax.lax.fori_loop(0, RT, row, 0)


# The resident d_src block of the backward: a whole-width block of the
# widest shipped shapes (7 x 384 x 512 and 7 x 256 x 768 float32: 5.5 MB,
# twice for the pipeline's two buffers) lets a unit's column window lie
# anywhere in the row, where two W_s tiles of 256 each splat every unit
# twice (LLFF full resolution, v5e, PR 29: 10.2 ms against 13.7). That is
# past Mosaic's default 16 MiB of scoped VMEM, of the chip's 128.
_DSRC_BLOCK_BUDGET = 8 * 1024 * 1024
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024


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
    NB = H_t // RT

    # EXACTLY the forward's band geometry and windows (kernels/warp.py
    # band_plan): ceil band, pad H so the clipped start stays covered,
    # floor-align the starts, the plan over the lane-padded width.
    xc, yc, band, pad_h, pad_w, y0, plan, _ = band_plan(
        src_shape, coords_x, coords_y, band, RT, unit_rows=RT)
    H_pad = H_s + pad_h
    tile, sub, kw = subband_geometry(band, W_t, W_s + pad_w, unit_rows=RT)
    NP = plan.shape[-1]

    TW = _pick_out_tile_w(C, H_pad, W_s, _DSRC_BLOCK_BUDGET)
    # a W_s tile no wider than the window (or an unpadded, unaligned width)
    # is splatted whole
    kw = min(kw, TW) if TW % LANE_ALIGN == 0 else TW
    kernel = functools.partial(_bwd_splat_kernel, C, band, sub, RT, TW,
                               tile, kw, mxu_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(Bp, W_s // TW, NB),  # row-blocks INNERMOST (see kernel doc)
        in_specs=[
            pl.BlockSpec((Bp, NB), lambda b, w, r: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, NP), lambda b, w, r: (b * NB + r, 0, 0),
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
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(y0, plan, g.astype(jnp.float32), xc, yc)
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


def guarded_subband_frac(src_shape, coords_x, coords_y, band: int = 48,
                         rows_per_block: int = 8) -> jnp.ndarray:
    """kernels/warp.subband_frac of a bilinear_sample_diff_guarded call:
    scalar f32, 0.0 where the call takes the gather fallback (no unit runs
    a kernel there)."""
    if coords_y.shape[1] % rows_per_block or src_shape[2] % rows_per_block:
        return jnp.zeros((), jnp.float32)
    return jnp.where(guard_ok(src_shape, coords_y, band, rows_per_block),
                     subband_frac(src_shape, coords_x, coords_y, band,
                                  rows_per_block), 0.0)


def bilinear_sample_diff_guarded(src, coords_x, coords_y,
                                 band: int = 48,
                                 rows_per_block: int = 8,
                                 interpret: bool = False,
                                 mxu_dtype=jnp.float32):
    """Banded differentiable warp with a runtime XLA-gather fallback.

    `lax.cond` on the (data-dependent, pose-derived) band-domain check: the
    Pallas fast path for translation-dominated warps, the gather (forward
    and its transpose, ops/warp._bilinear_sample_cast) for rotation-heavy
    ones. Both branches are differentiable, so this
    composes with jax.grad in the training step. Always returns float32
    (the kernel's accumulation dtype) so the two cond branches agree."""
    from mine_tpu.ops.warp import _bilinear_sample_cast, bilinear_sample

    # the gather fallback honors the same reduced-precision knob as the
    # kernel (mxu_dtype) via the f32-accumulating gather path, so fallback
    # steps keep the HBM-traffic benefit. Under the cond it is ALWAYS the
    # custom-VJP form, float32 included: its residuals are the coordinates,
    # as the kernel branch's are. The autodiffed gather would carry its four [B',H,W,2] int32 index
    # arrays out of the cond as residuals, and XLA is free to lay those out
    # with the 2 on the lane axis (64x padding: 6 GB each at 64x384x512,
    # hit when the plan table joined the kernel branch). Coordinates get
    # zero cotangents on both branches.
    gather_dtype = jnp.dtype(mxu_dtype).name
    src = src.astype(jnp.float32)
    H_t = coords_x.shape[1]
    if H_t % rows_per_block != 0 or src.shape[2] % rows_per_block != 0:
        return bilinear_sample(src, coords_x, coords_y,
                               gather_dtype=mxu_dtype)

    ok = guard_ok(src.shape, coords_y, band, rows_per_block)
    return jax.lax.cond(
        ok,
        lambda s, x, y: bilinear_sample_diff(
            s, x, y, band, rows_per_block, interpret, mxu_dtype),
        lambda s, x, y: _bilinear_sample_cast(s, x, y, gather_dtype),
        src, coords_x, coords_y)
