"""Pallas TPU kernel: banded bilinear gather for homography warping.

The reference's hot warp op is grid_sample over a B*S x 7 x H x W plane
volume (homography_sampler.py:138, called from mpi_rendering.py:214). On TPU
a per-pixel gather is the worst-case memory pattern; this kernel restructures
it around two TPU strengths:

  * the source rows a target row samples from lie in a narrow band (camera
    trajectories are translation-dominated; the plane-induced homography maps
    output rows to gently sloped source lines). Per block of RT output rows,
    the kernel DMAs one [C, BAND, W_s] source band from HBM into VMEM —
    sequential, coalesced traffic instead of scattered gathers.
  * within the band, bilinear interpolation is expressed as two small
    one-hot-weight contractions: an MXU matmul over the x axis
    ([C*BAND, W_s] @ [W_s, W_t] with at most two nonzeros per output column)
    and a VPU weighted reduction over the band's y axis. No gather
    instructions at all.

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


def _warp_kernel(C: int, BAND: int, RT: int, H_s: int, W_s: int,
                 mxu_dtype, y0_ref, xc_ref, yc_ref, src_ref, out_ref,
                 band_buf, sem):
    W_t = xc_ref.shape[2]
    # bf16 matmul operands compile only at lane-aligned output widths
    # (Mosaic "Bad lhs type" at W_t=48 on silicon, round-4 window; the
    # bench's W_t=384 was fine) — fall back to f32 elsewhere. No perf loss
    # in practice: the banded kernels measured VPU-bound, not MXU-bound.
    if W_t % 128:
        mxu_dtype = jnp.float32
    # y0 comes in as the FULL [B', NB] table in SMEM (a (1,1) block would
    # violate the Mosaic last-two-dims tiling rule); index it by grid step.
    # band_start aligns it to the sublane tile; multiple_of carries that
    # fact to Mosaic, which must PROVE dynamic HBM slice offsets aligned.
    y0 = pl.multiple_of(y0_ref[pl.program_id(0), pl.program_id(1)],
                        SUBLANE_ALIGN)

    # src arrives as the FULL array in HBM (ANY-space blocks must equal the
    # array shape); the batch index is applied here, the band via dynamic DMA
    dma = pltpu.make_async_copy(
        src_ref.at[pl.program_id(0), :, pl.ds(y0, BAND), :], band_buf, sem)
    dma.start()
    dma.wait()

    # mxu_dtype=bfloat16 halves the matmul operand width (2x MXU rate);
    # tent weights pick up ~2^-8 relative rounding, accumulation stays f32
    band = band_buf[:].reshape(C * BAND, W_s).astype(mxu_dtype)
    # Mosaic iota must be integer-typed; cast to f32 for the tent weights
    xs = jax.lax.broadcasted_iota(jnp.int32, (W_s, W_t), 0).astype(jnp.float32)
    ys = jax.lax.broadcasted_iota(jnp.int32, (BAND, W_t), 0).astype(jnp.float32)

    for r in range(RT):
        sx = xc_ref[0, r:r + 1, :]                      # [1, W_t]
        sy = yc_ref[0, r:r + 1, :] - y0.astype(jnp.float32)
        sy = jnp.clip(sy, 0.0, BAND - 1.0)              # band coverage clamp

        wx = jnp.maximum(1.0 - jnp.abs(xs - sx), 0.0)   # [W_s, W_t]
        t = jnp.dot(band, wx.astype(mxu_dtype),
                    preferred_element_type=jnp.float32)
        t = t.reshape(C, BAND, W_t)
        wy = jnp.maximum(1.0 - jnp.abs(ys - sy), 0.0)   # [BAND, W_t]
        out_ref[0, :, r, :] = jnp.sum(t * wy[None], axis=1)


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
    Bp, C, H_s, W_s = src.shape
    _, H_t, W_t = coords_x.shape
    RT = rows_per_block
    assert H_t % RT == 0, (H_t, RT)
    NB = H_t // RT
    # a band taller than the source would DMA past the image; shrink it (the
    # whole image then fits in VMEM, which is exactly the right behavior)
    band = min(band, H_s)

    xc = jnp.clip(coords_x, 0.0, W_s - 1.0).astype(jnp.float32)
    yc = jnp.clip(coords_y, 0.0, H_s - 1.0).astype(jnp.float32)

    # Mosaic constraints (hit on silicon, round-4 window): HBM slices of
    # the (8,128)-tiled source must have 128-aligned lane width AND
    # 8-aligned sublane offset/size. Pad the SOURCE (mosaic_band_geometry
    # docstring): padded columns get exactly-zero tent weights (xc is
    # clipped to the true W_s-1, so |xs - sx| >= 1 there), and padded rows
    # likewise sit >= 1 row beyond the yc clip range — numerics unchanged.
    band, pad_h, pad_w = mosaic_band_geometry(band, H_s, W_s)
    if pad_h or pad_w:
        src = jnp.pad(src, ((0, 0), (0, 0), (0, pad_h), (0, pad_w)))
    H_pad, W_s = src.shape[2], src.shape[3]

    y0 = band_start(yc, H_pad, band, RT)  # [B', NB]
    # Sublane-align the dynamic DMA start (Mosaic must prove divisibility;
    # see pl.multiple_of in the kernel). Floor only moves the start UP the
    # image — ≤7 rows of headroom, accounted by fwd_domain_ok's slack —
    # and the clip bound (H_pad - band) is itself aligned, so the bottom
    # of the image stays covered. The XLA banded backend keeps the
    # unaligned band_start (no Mosaic constraint); values agree wherever
    # both bands cover, which the shared domain guard guarantees.
    y0 = (y0 // SUBLANE_ALIGN) * SUBLANE_ALIGN

    grid = (Bp, NB)
    kernel = functools.partial(_warp_kernel, C, band, RT, H_pad, W_s,
                               mxu_dtype)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((Bp, NB), lambda b, r: (0, 0),
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
            pltpu.VMEM((C, band, W_s), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
        name="warp_bilinear_sample_fwd",
        interpret=interpret,
    )(y0, xc, yc, src.astype(jnp.float32))


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


def mosaic_band_geometry(band: int, extent: int, lane_extent: int):
    """THE Mosaic alignment recipe, shared by the forward wrapper and the
    VJP's backward wrapper so their domains can never desynchronize:

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


def band_start(coords_y_clipped: jnp.ndarray, H_s: int, band: int,
               rows_per_block: int = 8) -> jnp.ndarray:
    """Band start row per (plane, row-block): floor of the block's min
    source row, clipped so the band stays inside the image. [B', NB] i32.

    THE band placement rule — shared by the Pallas forward kernel and the
    pure-XLA banded warp. The Pallas wrapper additionally sublane-aligns
    the result (after padding H so the clip bound is itself aligned); the
    XLA path needs no alignment. Both compute exact bilinear values inside
    their band, so the backends agree wherever the shared domain guard
    (fwd_domain_ok, which budgets the Pallas alignment slack) passes.
    """
    Bp, H_t, W_t = coords_y_clipped.shape
    NB = H_t // rows_per_block
    y_blocks = coords_y_clipped.reshape(Bp, NB, rows_per_block * W_t)
    y0 = jnp.floor(jnp.min(y_blocks, axis=2)).astype(jnp.int32)
    return jnp.clip(y0, 0, max(H_s - band, 0))


def fwd_domain_ok(coords_y: jnp.ndarray, H_s: int, band: int,
                  rows_per_block: int = 8,
                  aligned: bool = True) -> jnp.ndarray:
    """Scalar bool (jit-safe): every row-block's source span fits the band.

    THE definition of the banded forward's correctness domain (span + 2
    rows of bilinear support + the sublane-alignment slack must fit the
    band, clamped to the image) — shared by the Pallas VJP guard
    (kernels/warp_vjp.py) and the pure-XLA banded warp (ops/warp_banded.py)
    so the two backends can never diverge on which poses count as in-band.
    coords_y must be border-clipped.

    `aligned=False` drops the sublane-alignment slack from the budget: the
    pure-XLA banded path keeps unaligned band starts (band_start docstring),
    so it covers poses within SUBLANE_ALIGN-1 rows of the band limit that
    the Pallas wrapper must send to the fallback.
    """
    eff = min(band, H_s)
    slack = _align_slack(eff, H_s) if aligned else 0
    return band_span(coords_y, H_s, rows_per_block) + 2.0 <= eff - slack


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
