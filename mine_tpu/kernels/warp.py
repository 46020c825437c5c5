"""Pallas TPU kernel: banded bilinear gather for homography warping.

The reference's hot warp op is grid_sample over a B*S x 7 x H x W plane
volume (homography_sampler.py:138, called from mpi_rendering.py:214); since
PR 36 the volume here is B*S x 4 x H x W (rgb + sigma: the plane points are
a formula at the same coordinates, ops/rendering.py; the kernel is generic
in C). On TPU a per-pixel gather is the worst-case memory pattern; this
kernel restructures it around the TPU's strengths:

  * the source rows a target row samples from lie in a narrow band (camera
    trajectories are translation-dominated; the plane-induced homography maps
    output rows to gently sloped source lines). Per block of RT output rows,
    the kernel DMAs one [C, BAND, W_s] source band from HBM into VMEM —
    sequential, coalesced traffic instead of scattered gathers.
  * within the band, bilinear interpolation is expressed as two small
    one-hot-weight contractions: an MXU matmul over the x axis (at most two
    nonzeros per output column) and a VPU weighted reduction over the
    band's y axis. No gather instructions at all.
  * the band is sized for a BLOCK of rows across the full width; one output
    row's taps on one lane tile of 128 columns reach far less of it. Each
    such unit multiplies a sublane-aligned SUB-BAND of 16 rows and a
    lane-aligned WINDOW of source columns, [C*16, K] @ [K, 128], placed per
    unit by `subband_plan`; the rows and columns left out carry tent
    weights of exactly 0, so the sum is the one the whole band gives. A
    unit whose taps do not fit (steep rotation) is contracted against the
    whole band instead: every in-domain pose stays on the kernel. On a v5e
    the whole-band form ran the MXU at three quarters of its bf16 peak on
    this one-hot matrix (ledger, PR 28: `warp_roofline.*` 3.9-4.9%); the
    windowed form takes 9.3 ms against 28.1 at 64x7x384x512, band 48 (my
    chip run, PR 29; 7.8 with its lane-tile loop unrolled, _lane_tiles)
    and is bit-identical there. At the four channels the step warps since
    PR 36 it takes 6.1 ms in llff_train's step against 8.0 for seven (my
    chip run, PR 36; 1.29 / 1.60 at 192x256): about 3.6 ms of a call do
    not scale with C (the tent weights' build and, by the product's shape,
    the MXU loading each unit's [K, 128] weights for 64 streamed rows where
    it had 112; the two were not measured apart).

Correctness domain: a row-block's source y-span must fit in BAND-2 rows
(after clamping to the image). The span includes the block's own extent —
RT output rows map to ~RT source rows under near-identity warps — so BAND
must exceed RT; the default (RT=8, BAND=16) leaves ~6 rows of slope/shear
headroom per block. `band_span` computes the actual span for a coordinate
field so callers with host-known poses (e.g. the video renderer) can pick
the kernel or the XLA path per call. Coordinates outside the image follow
grid_sample(border) semantics, matching ops/warp.bilinear_sample.
This module is the forward kernel; kernels/warp_vjp.py pairs it with a
transposed-band backward kernel (custom VJP) so training can use it too
(`training.warp_backend: pallas_diff`).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _tent(d):
    """Bilinear tent weight of an offset field: max(1 - |d|, 0)."""
    return jnp.maximum(1.0 - jnp.abs(d), 0.0)


def _f32(v):
    return jnp.asarray(v, jnp.float32)


def _iota_rows(n: int, width: int):
    """[n, width] f32 row index (Mosaic iota must be integer-typed; cast
    for the tent weights)."""
    return jax.lax.broadcasted_iota(jnp.int32, (n, width), 0).astype(
        jnp.float32)


def _lane_tiles(T: int, TILE: int, body, unroll: bool = False):
    """body(j, lanes) for every lane tile of a kernel's output width, as a
    loop: Python traces the body once and not T times, and its lane offsets
    are multiples of the tile, which Mosaic must be told.

    `unroll` trades set-up for speed. Pallas lowers an unrolled loop by
    lowering its body T times, in Python, in every program that holds the
    kernel and at every start of the process (a compile-cache hit skips
    the compiler, not the lowering): 1.0 s a program for the forward at T =
    4 on the chip's host against 0.08 s for the whole-band kernel, +12 s of
    warm set-up over the serve cell's ten render programs. The straight run
    of code is faster on the device: the 64x7x384x512 pair takes 7.8 + 10.1
    ms unrolled, 9.3 + 11.7 as loops (v5e, PR 29; channels were 7 then). So the forward, which
    serving starts many programs of, loops; the backward, which only the
    train step holds, unrolls."""
    if T == 1:
        return body(0, slice(0, TILE))

    def step(j, carry):
        body(j, pl.ds(pl.multiple_of(j * TILE, TILE), TILE))
        return carry

    jax.lax.fori_loop(0, T, step, 0, unroll=unroll)


def _pick_row(block, r):
    """Row r (dynamic) of a [..., RT, TILE] block, kept as [..., 1, TILE]: a
    masked sum, since Mosaic loads no single row at a dynamic, unaligned
    sublane index."""
    row_id = jax.lax.broadcasted_iota(jnp.int32, block.shape[-2:], 0)
    return jnp.sum(jnp.where(row_id == r, block, 0.0), axis=-2,
                   keepdims=True)


def _warp_kernel(C: int, BAND: int, SUB: int, RT: int, TILE: int, KW: int,
                 mxu_dtype, y0_ref, plan_ref, xc_ref, yc_ref, src_ref,
                 out_ref, band_buf, sem):
    """Grid step (b, target-row-block): wait for the block's band (its DMA
    was started a step ahead), then contract every (output row, lane tile)
    unit against the SUB band rows and KW source columns its taps can reach
    (subband_plan places both); a unit whose taps leave that window is
    contracted again against the whole band, as before the windowed form
    existed. The rows and columns a window leaves out carry a tent weight
    of exactly 0, so both paths give the same sum."""
    W_t = xc_ref.shape[2]
    W_s = band_buf.shape[3]
    T = W_t // TILE
    U = RT * T
    # bf16 matmul operands compile only at lane-aligned output widths
    # (Mosaic "Bad lhs type" at W_t=48 on silicon, round-4 window; the
    # bench's W_t=384 was fine) — fall back to f32 elsewhere.
    if W_t % 128:
        mxu_dtype = jnp.float32
    NB = pl.num_programs(1)
    step = pl.program_id(0) * NB + pl.program_id(1)
    slot = jax.lax.rem(step, 2)

    def band_dma(step, slot):
        # y0 comes in as the FULL [B', NB] table in SMEM (a (1,1) block
        # would violate the Mosaic last-two-dims tiling rule); index it by
        # grid step. _aligned_band_start aligns it to the sublane tile;
        # multiple_of carries that fact to Mosaic, which must PROVE dynamic
        # HBM slice offsets aligned. src arrives as the FULL array in HBM (ANY-space
        # blocks must equal the array shape); the batch index is applied
        # here, the band via dynamic DMA
        b, nb = jax.lax.div(step, NB), jax.lax.rem(step, NB)
        y0 = pl.multiple_of(y0_ref[b, nb], SUBLANE_ALIGN)
        return y0, pltpu.make_async_copy(
            src_ref.at[b, :, pl.ds(y0, BAND), :], band_buf.at[slot],
            sem.at[slot])

    # the grid runs in order on one core: each step starts the NEXT block's
    # band into the other buffer before it waits for its own, so the copy
    # (2.6 GB a full-resolution training call) hides behind the contraction
    @pl.when(step == 0)
    def _first():
        band_dma(step, slot)[1].start()

    @pl.when(step + 1 < pl.num_programs(0) * NB)
    def _next():
        band_dma(step + 1, 1 - slot)[1].start()

    y0, dma = band_dma(step, slot)
    dma.wait()
    y0f = y0.astype(jnp.float32)

    iota_rows = functools.partial(_iota_rows, width=TILE)

    def contract(rows, wx, wy):
        """[C, n, K] band rows x [K, TILE] column weights on the MXU, then
        the [n, TILE] row weights on the VPU: [C, TILE].
        mxu_dtype=bfloat16 halves the matmul operand width; tent weights
        pick up ~2^-8 relative rounding, accumulation stays f32."""
        n = rows.shape[1]
        t = jnp.dot(rows.reshape(C * n, rows.shape[2]).astype(mxu_dtype),
                    wx.astype(mxu_dtype), preferred_element_type=jnp.float32)
        return jnp.sum(t.reshape(C, n, TILE) * wy[None], axis=1)

    def band_rel(sy):
        # band coverage clamp (min / max, not jnp.clip: a nested jit in
        # every unit is paid at every lowering of the kernel)
        return jnp.minimum(jnp.maximum(sy - y0f, 0.0), BAND - 1.0)

    xs, ys = iota_rows(KW), iota_rows(SUB)

    def windowed(j, lanes):
        sx_rows, sy_rows = xc_ref[0, :, lanes], band_rel(yc_ref[0, :, lanes])
        for r in range(RT):
            u = r * T + j
            # both starts are tile-aligned by subband_plan; multiple_of
            # carries that to Mosaic for the dynamic VMEM slices
            s0 = pl.multiple_of(plan_ref[0, 0, u], SUBLANE_ALIGN) \
                if SUB < BAND else 0
            k0 = pl.multiple_of(plan_ref[0, 0, U + u], LANE_ALIGN) \
                if KW < W_s else 0
            rows = band_buf[slot, :, pl.ds(s0, SUB), pl.ds(k0, KW)]
            wx = _tent(xs - (sx_rows[r:r + 1] - _f32(k0)))  # [KW, TILE]
            wy = _tent(ys - (sy_rows[r:r + 1] - _f32(s0)))  # [SUB, TILE]
            out_ref[0, :, r, lanes] = contract(rows, wx, wy)

    _lane_tiles(T, TILE, windowed)

    @pl.when(plan_ref[0, 0, 3 * U] != 0)   # a unit of this block overflows
    def _whole_band():
        # the rare path: loops over rows and tiles keep it small; the row
        # is picked by a masked sum and put by a masked merge (_pick_row)
        xs_all, ys_all = iota_rows(W_s), iota_rows(BAND)
        row_id = jax.lax.broadcasted_iota(jnp.int32, (RT, TILE), 0)

        def unit(r, j, lanes):
            @pl.when(plan_ref[0, 0, 2 * U + r * T + j] == 0)
            def _overflows():
                sx = _pick_row(xc_ref[0, :, lanes], r)
                sy = band_rel(_pick_row(yc_ref[0, :, lanes], r))
                val = contract(band_buf[slot], _tent(xs_all - sx),
                               _tent(ys_all - sy))
                out_ref[0, :, :, lanes] = jnp.where(
                    row_id[None] == r, val[:, None],
                    out_ref[0, :, :, lanes])

        def row(r, carry):
            _lane_tiles(T, TILE, functools.partial(unit, r))
            return carry

        jax.lax.fori_loop(0, RT, row, 0)


@functools.partial(jax.jit,
                   static_argnames=("band", "rows_per_block", "interpret",
                                    "mxu_dtype"))
def pallas_bilinear_sample(src: jnp.ndarray,
                           coords_x: jnp.ndarray,
                           coords_y: jnp.ndarray,
                           band: int = 16,
                           rows_per_block: int = 8,
                           interpret: bool = False,
                           mxu_dtype=jnp.float32) -> jnp.ndarray:
    """Banded-gather equivalent of ops.warp.bilinear_sample.

    Args:
      src: [B', C, H_s, W_s]
      coords_x, coords_y: [B', H_t, W_t] source pixel coordinates
      mxu_dtype: matmul operand dtype (jnp.bfloat16 doubles MXU rate at
        ~2^-8 relative weight rounding; accumulation is always f32)
    Returns: [B', C, H_t, W_t]
    """
    Bp, C, _, _ = src.shape
    _, H_t, W_t = coords_x.shape
    RT = rows_per_block
    NB = H_t // RT
    xc, yc, band, pad_h, pad_w, y0, plan, _ = band_plan(
        src.shape, coords_x, coords_y, band, RT)
    # Pad the SOURCE to the Mosaic geometry (_mosaic_band_geometry
    # docstring): padded columns get exactly-zero tent weights (xc is
    # clipped to the true W_s-1, so |xs - sx| >= 1 there), and padded rows
    # likewise sit >= 1 row beyond the yc clip range — numerics unchanged.
    if pad_h or pad_w:
        src = jnp.pad(src, ((0, 0), (0, 0), (0, pad_h), (0, pad_w)))
    H_pad, W_s = src.shape[2], src.shape[3]
    tile, sub, kw = subband_geometry(band, W_t, W_s)
    NP = plan.shape[-1]

    grid = (Bp, NB)
    kernel = functools.partial(_warp_kernel, C, band, sub, RT, tile, kw,
                               mxu_dtype)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((Bp, NB), lambda b, r: (0, 0),
                         memory_space=pltpu.SMEM),
            # the plan of ONE block: [B', H_t, tiles] entries are ~400 KB at
            # 64x384x4 and do not fit SMEM whole; the unit leading dims make
            # the block's last two dims the array's own (Mosaic tiling rule)
            pl.BlockSpec((1, 1, NP), lambda b, r: (b * NB + r, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, RT, W_t), lambda b, r: (b, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, RT, W_t), lambda b, r: (b, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Bp, C, H_pad, W_s), lambda b, r: (0, 0, 0, 0),
                         memory_space=pl.ANY),  # stays in HBM; banded DMA
        ],
        out_specs=pl.BlockSpec((1, C, RT, W_t), lambda b, r: (b, 0, r, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Bp, C, H_t, W_t), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, C, band, W_s), jnp.float32),  # double buffer
            pltpu.SemaphoreType.DMA((2,)),
        ],
        name="warp_bilinear_sample_fwd",
        interpret=interpret,
    )(y0, plan, xc, yc, src.astype(jnp.float32))


# Dynamic HBM slice offsets must be provably divisible by the sublane tile
# (8 for f32 (8,128)-tiled memrefs — all banded-warp DMA operands are cast
# to f32). Hit on silicon at bench shapes (round-4 window): Mosaic rejects
# an unaligned dynamic band start. Aligning the start DOWN costs at most
# SUBLANE_ALIGN-1 rows of band headroom (accounted in the domain guards)
# and is semantically free: band placement doesn't change values as long
# as every needed source row stays in-band.
SUBLANE_ALIGN = 8


LANE_ALIGN = 128  # lane (last-dim) tile of f32/bf16 TPU memrefs


def _align_slack(window: int, extent: int) -> int:
    """Band-headroom rows consumed by sublane alignment (0 when the window
    covers the whole extent — the start is then always 0, which is aligned)."""
    return 0 if window >= extent else SUBLANE_ALIGN - 1


def _mosaic_band_geometry(band: int, extent: int, lane_extent: int):
    """THE Mosaic alignment recipe (band_plan hands it to the forward and
    the backward wrapper alike, so their domains can never desynchronize):

      * ceil the band to the sublane tile (slice SIZE must be aligned),
      * pad the banded (row) extent so the band-start clip bound
        (extent_padded - band) is itself aligned — the clipped-start case
        then stays covered, the band running into padding instead of
        uncovering the last rows,
      * pad the lane extent to the lane tile (slice WIDTH must be aligned).

    Returns (band, pad_rows, pad_lanes).
    """
    band = -((-band) // SUBLANE_ALIGN) * SUBLANE_ALIGN
    pad_rows = max((-extent) % SUBLANE_ALIGN, band - extent)
    pad_lanes = (-lane_extent) % LANE_ALIGN
    return band, pad_rows, pad_lanes


def _aligned_band_start(coords_y_clipped: jnp.ndarray, H_pad: int, band: int,
                        rows_per_block: int = 8) -> jnp.ndarray:
    """Band start row per (plane, row-block), [B', NB] i32: floor of the
    block's min source row, clipped so the band stays inside the (padded)
    image, then floored to the sublane tile — the kernels' DMA and VMEM
    slice starts (Mosaic must prove divisibility; see pl.multiple_of in the
    kernels). The floor only moves the start UP the image — ≤7 rows of
    headroom, accounted by fwd_domain_ok's slack — and the clip bound
    (H_pad - band, _mosaic_band_geometry) is itself aligned, so the bottom
    of the image stays covered. Band placement does not change values as
    long as every needed source row stays in-band."""
    Bp, H_t, W_t = coords_y_clipped.shape
    NB = H_t // rows_per_block
    y_blocks = coords_y_clipped.reshape(Bp, NB, rows_per_block * W_t)
    y0 = jnp.floor(jnp.min(y_blocks, axis=2)).astype(jnp.int32)
    y0 = jnp.clip(y0, 0, max(H_pad - band, 0))
    return (y0 // SUBLANE_ALIGN) * SUBLANE_ALIGN


def subband_geometry(band: int, W_t: int, W_src: int, unit_rows: int = 1):
    """Static sizes of the windowed contraction, from shapes and the band
    alone: (lane tile of the output width, sub-band rows, source-column
    window). A unit is `unit_rows` output rows x one lane tile: one row in
    the forward kernel, the block's rows_per_block in the backward (which
    sums a block's rows inside one matmul).

      * the output width is cut into lane tiles of 128 (one tile where the
        width is no multiple of 128: the pyramid's narrow levels);
      * a unit's taps span its own rows, the slope across the tile (5-7
        rows at rotations up to 0.04 rad between two views) and 2 rows of
        bilinear support, and its sub-band starts on the sublane tile (7
        rows of slack): 16 rows for one output row, 24 for eight, or the
        band if smaller;
      * a tile's taps span about its own width in source columns at unit
        magnification, and its window starts on the lane tile (127 columns
        of slack): the tile plus two lane tiles, or the source width if
        that is no wider (then nothing is cut: W_s <= 384).
    """
    tile = LANE_ALIGN if W_t % LANE_ALIGN == 0 else W_t
    sub = min(band, -(-(unit_rows + 15) // SUBLANE_ALIGN) * SUBLANE_ALIGN)
    kw = W_src
    if W_src % LANE_ALIGN == 0:
        kw = min(W_src, -(-tile // LANE_ALIGN) * LANE_ALIGN + 2 * LANE_ALIGN)
    return tile, sub, kw


def subband_plan(xc: jnp.ndarray, yc: jnp.ndarray, y0: jnp.ndarray,
                 band: int, W_src: int, rows_per_block: int = 8,
                 unit_rows: int = 1):
    """Where each (unit_rows output rows, lane tile) unit's window sits
    inside its block's band, and whether the unit's taps fit it.

    xc, yc: border-clipped coords [B', H_t, W_t]; y0: the blocks' aligned
    band starts [B', NB]; band, W_src: the kernel's band rows and source
    columns (after _mosaic_band_geometry). Returns

      plan [B' * NB, 1, 3 * U + 1] int32, U = units a block: per block the
        units' sub-band starts (band-relative, sublane-aligned),
        column-window starts (lane-aligned), fits flags, and one flag
        "some unit of this block does not fit" — the kernels' SMEM table,
        one row a grid step;
      fits [B', H_t // unit_rows, tiles] bool: the unit runs the windowed
        contraction alone.

    A unit fits when the rows floor(min sy) .. ceil(max sy) lie inside its
    sub-band and the columns floor(min sx) .. ceil(max sx) inside its
    window: every other row and column has a tent weight of exactly 0.
    """
    Bp, H_t, W_t = yc.shape
    RT = rows_per_block
    NB = H_t // RT
    tile, sub, kw = subband_geometry(band, W_t, W_src, unit_rows)
    T = W_t // tile

    def unit_range(c):
        c = c.reshape(Bp, H_t // unit_rows, unit_rows, T, tile)
        return (jnp.floor(jnp.min(c, axis=(2, 4))).astype(jnp.int32),
                jnp.ceil(jnp.max(c, axis=(2, 4))).astype(jnp.int32))

    # the kernels' own band-relative row coordinate, coverage clamp included
    y_band = jnp.clip(yc - jnp.repeat(y0, RT, axis=1)[:, :, None].astype(
        jnp.float32), 0.0, band - 1.0)
    y_lo, y_hi = unit_range(y_band)
    s0 = jnp.minimum((y_lo // SUBLANE_ALIGN) * SUBLANE_ALIGN, band - sub)
    x_lo, x_hi = unit_range(xc)
    k0 = jnp.minimum((x_lo // LANE_ALIGN) * LANE_ALIGN, W_src - kw)
    fits = (y_hi - s0 <= sub - 1) & (x_hi - k0 <= kw - 1)

    def per_block(v):
        return v.astype(jnp.int32).reshape(Bp * NB, RT // unit_rows * T)

    overflow = 1 - jnp.min(per_block(fits), axis=1, keepdims=True)
    plan = jnp.concatenate(
        [per_block(s0), per_block(k0), per_block(fits), overflow], axis=1)
    return plan[:, None, :], fits


def band_plan(src_shape, coords_x: jnp.ndarray, coords_y: jnp.ndarray,
              band: int, rows_per_block: int = 8, unit_rows: int = 1):
    """Everything the banded pair derives from coordinates, in one place so
    the forward, the backward and the warp_subband_frac counter can never
    disagree: border-clipped coords, the Mosaic band geometry, the aligned
    band starts and the windows' plan.

    Returns (xc, yc, band, pad_rows, pad_lanes, y0 [B', NB], plan, fits);
    plan and fits as subband_plan gives them, over the padded source."""
    _, _, H_s, W_s = src_shape
    H_t = coords_x.shape[1]
    assert H_t % rows_per_block == 0, (H_t, rows_per_block)
    # a band taller than the source would DMA past the image; shrink it (the
    # whole image then fits in VMEM, which is exactly the right behavior)
    band = min(band, H_s)
    xc = jnp.clip(coords_x, 0.0, W_s - 1.0).astype(jnp.float32)
    yc = jnp.clip(coords_y, 0.0, H_s - 1.0).astype(jnp.float32)
    # Mosaic constraints (hit on silicon, round-4 window): HBM slices of
    # the (8,128)-tiled source must have 128-aligned lane width AND
    # 8-aligned sublane offset/size.
    band, pad_h, pad_w = _mosaic_band_geometry(band, H_s, W_s)
    y0 = _aligned_band_start(yc, H_s + pad_h, band, rows_per_block)
    plan, fits = subband_plan(xc, yc, y0, band, W_s + pad_w, rows_per_block,
                              unit_rows)
    return xc, yc, band, pad_h, pad_w, y0, plan, fits


def subband_frac(src_shape, coords_x: jnp.ndarray, coords_y: jnp.ndarray,
                 band: int, rows_per_block: int = 8) -> jnp.ndarray:
    """Scalar f32 (jit-safe): the share of a call's (output row, lane tile)
    units that the forward kernel contracts against their window alone; the
    rest also run the whole-band contraction. The `warp_subband_frac`
    training metric: 1.0 at translation-dominated poses, falling as
    rotation widens a tile's source-row span past the sub-band. (The
    backward's units are a block's rows x a lane tile, in a sub-band of 24:
    where the forward's rows fit theirs, so as a rule does the block.)"""
    fits = band_plan(src_shape, coords_x, coords_y, band, rows_per_block)[-1]
    return jnp.mean(fits.astype(jnp.float32))


def fwd_domain_ok(coords_y: jnp.ndarray, H_s: int, band: int,
                  rows_per_block: int = 8) -> jnp.ndarray:
    """Scalar bool (jit-safe): every row-block's source span fits the band.

    THE definition of the banded pair's correctness domain (span + 2 rows
    of bilinear support + the sublane-alignment slack must fit the band,
    clamped to the image); the VJP guard (kernels/warp_vjp.py) is this.
    coords_y must be border-clipped.
    """
    eff = min(band, H_s)
    return (band_span(coords_y, H_s, rows_per_block) + 2.0
            <= eff - _align_slack(eff, H_s))


def band_span(coords_y: jnp.ndarray, H_s: int,
              rows_per_block: int = 8) -> jnp.ndarray:
    """Max per-row-block source-row span (rows needed = span + 2, plus the
    sublane-alignment slack when the Pallas kernel is the target).

    Callers check `band_span(...) + 2 + _align_slack(band, H_s) <= band`
    before choosing the kernel (fwd_domain_ok is the jit-safe form; the
    video renderer applies the same rule to its numpy span estimate); with
    host-known poses this is a cheap numpy decision per chunk.
    """
    Bp, H_t, W_t = coords_y.shape
    NB = H_t // rows_per_block
    yc = jnp.clip(coords_y, 0.0, H_s - 1.0)
    yb = yc.reshape(Bp, NB, rows_per_block * W_t)
    return jnp.max(jnp.max(yb, axis=2) - jnp.min(yb, axis=2))
