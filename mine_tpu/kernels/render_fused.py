"""Pallas TPU megakernel: warp -> dequant -> composite -> blend in one pass.

The serve hot path (r05 bench notes) runs as separate XLA programs: a
fused-dequant step materializes the full float plane volume in HBM, the
homography warp gathers it, and the sigma-density composite streams it
again — three round trips over the largest tensor in the request. This
module collapses them into ONE kernel over the target row-blocks:

  * per plane, a banded DMA pulls the CACHED (f32/bf16/int8) plane rows
    straight from HBM — the quantized form is what crosses the wire; the
    full-volume float intermediate never exists,
  * dequantization happens in registers (int8 per-plane-per-channel scales
    live in SMEM; bf16 widens for free on the way into the VPU),
  * the banded tent-weight warp (kernels/warp.py) resolves the bilinear
    sample as an MXU matmul + VPU band reduction,
  * the sigma-density transparency composite (kernels/composite.py
    _tgt_kernel op sequence, including the behind-camera z-mask and the
    reference's +1e-6 cumprod stabilizer) accumulates rgb/depth in
    registers, carried across the statically-unrolled plane loop.

Net HBM traffic: one banded read of the cached volume + xyz field, one
write of the composited rgb/depth. The N-plane volume stays HBM-resident
throughout (pl.ANY placement, per-plane banded DMA).

Correctness domain: every plane's row-block source span must fit the band
(kernels/warp.py geometry, generalized to the CACHE dtype's sublane tile —
int8 memrefs tile (32,128), bf16 (16,128), f32 (8,128), so the band, the
row padding and the dynamic DMA start all align to the widest tile in
play). `fused_domain_ok` is the jit-safe guard; `fused_plane_render_guarded`
wraps the kernel in the house `lax.cond` pattern with the XLA
dequant->gather->composite graph (`xla_reference_render`, bitwise the same
structure as the `backend="xla"` path) as the fallback branch, and a
custom_vjp twin (kernels/warp_sep.py pattern) makes the guarded call
trainable: the forward runs the megakernel, the backward differentiates
the XLA-equivalent graph (coords get zero cotangents — every caller
stop-gradients them; see ops/warp.py).

Parity with the XLA composite path is test-gated (tests/test_render_fused,
house tolerances); the dequant LOCATION is pinned bitwise — reading the
quantized planes inside the kernel equals pre-dequantized planes through
the same kernel exactly, for all three cache quant modes.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mine_tpu.kernels.warp import LANE_ALIGN, band_span


def sublane_align(dtype) -> int:
    """Sublane tile of a TPU memref at `dtype`: the divisibility Mosaic
    must prove for dynamic HBM slice offsets/sizes. f32 tiles (8,128),
    bf16 (16,128), int8 (32,128) — the banded DMA of the CACHED volume
    slices at the cache dtype, so the fused geometry aligns to it (the f32
    xyz field rides the same, coarser alignment for free)."""
    return {4: 8, 2: 16, 1: 32}[jnp.dtype(dtype).itemsize]


def fused_band_geometry(band: int, extent: int, lane_extent: int,
                        align: int) -> Tuple[int, int, int]:
    """kernels/warp.py mosaic_band_geometry generalized to the cache
    dtype's sublane tile: ceil the band to `align`, pad rows so the
    band-start clip bound stays aligned, pad lanes to the 128 tile.
    Returns (band, pad_rows, pad_lanes)."""
    band = -((-band) // align) * align
    pad_rows = max((-extent) % align, band - extent)
    pad_lanes = (-lane_extent) % LANE_ALIGN
    return band, pad_rows, pad_lanes


def fused_domain_ok(vol_shape, vol_dtype, coords_y: jnp.ndarray,
                    band: int, rows_per_block: int = 8) -> jnp.ndarray:
    """Scalar bool (jit-safe): the megakernel computes exact banded values
    for these coords. Same span rule as kernels/warp.fwd_domain_ok, with
    the alignment slack budgeted at the CACHE dtype's sublane tile (an
    int8 cache aligns band starts to 32 rows, so up to 31 rows of headroom
    go to alignment instead of slope). coords_y is [B,S,H_t,W_t] or
    [B*S,H_t,W_t], unclipped or clipped — band_span clips internally."""
    H_s = vol_shape[-2]
    H_t = coords_y.shape[-2]
    if H_t % rows_per_block:
        return jnp.zeros((), jnp.bool_)
    align = sublane_align(vol_dtype)
    eff = min(band, H_s)
    eff, pad_h, _ = fused_band_geometry(eff, H_s, 1, align)
    slack = 0 if eff >= H_s + pad_h else align - 1
    cy = coords_y.reshape(-1, H_t, coords_y.shape[-1])
    return band_span(cy, H_s, rows_per_block) + 2.0 <= eff - slack


def _fused_kernel(S: int, BAND: int, RT: int, W_s: int, dequant: bool,
                  is_bg_depth_inf: bool, align: int,
                  y0_ref, scale_ref, xc_ref, yc_ref, vol_ref, xyz_ref,
                  rgb_out, depth_out, vol_band, xyz_band, vsem, xsem):
    """One (view, row-block) grid cell: S-plane loop of banded DMA ->
    register dequant -> tent-weight warp -> streaming composite."""
    b = pl.program_id(0)
    nb = pl.program_id(1)
    W_t = xc_ref.shape[3]
    xs = jax.lax.broadcasted_iota(jnp.int32, (W_s, W_t), 0).astype(jnp.float32)
    ys = jax.lax.broadcasted_iota(jnp.int32, (BAND, W_t), 0).astype(jnp.float32)

    t_acc = jnp.ones((RT, W_t), jnp.float32)
    acc_rgb = jnp.zeros((3, RT, W_t), jnp.float32)
    acc_d = jnp.zeros((RT, W_t), jnp.float32)
    acc_w = jnp.zeros((RT, W_t), jnp.float32)
    prev = None  # (rgb [3,RT,W_t], sigma [RT,W_t], xyz [3,RT,W_t])

    def composite_step(plane, dist, accs):
        # kernels/composite.py _tgt_kernel op sequence, z_mask always on
        # (the xla path masks behind-camera density unconditionally)
        t_acc, acc_rgb, acc_d, acc_w = accs
        rgb_p, sig_p, xyz_p = plane
        sig = jnp.where(xyz_p[2] >= 0.0, sig_p, 0.0)
        trans = jnp.exp(-sig * dist)
        w = t_acc * (1.0 - trans)
        acc_rgb = acc_rgb + w[None] * rgb_p
        acc_d = acc_d + w * xyz_p[2]
        acc_w = acc_w + w
        t_acc = t_acc * (trans + 1e-6)
        return t_acc, acc_rgb, acc_d, acc_w

    for s in range(S):
        y0 = pl.multiple_of(y0_ref[b * S + s, nb], align)
        dma_v = pltpu.make_async_copy(
            vol_ref.at[b, s, :, pl.ds(y0, BAND), :], vol_band, vsem)
        dma_x = pltpu.make_async_copy(
            xyz_ref.at[b, s, :, pl.ds(y0, BAND), :], xyz_band, xsem)
        dma_v.start()
        dma_x.start()
        dma_v.wait()
        dma_x.wait()

        # in-register dequant: the only float form of the cached planes.
        # int8 scales are per-(plane, channel) SMEM scalars; bf16/f32 skip
        # the multiply entirely (dequant is static) so the widening cast
        # stays bitwise.
        v = vol_band[:].astype(jnp.float32)
        if dequant:
            v = jnp.stack([v[c] * scale_ref[b * S + s, c] for c in range(4)])
        band7 = jnp.concatenate([v, xyz_band[:]], axis=0)
        flat = band7.reshape(7 * BAND, W_s)

        rows = []
        for r in range(RT):
            sx = xc_ref[0, s, r:r + 1, :]                  # [1, W_t]
            sy = yc_ref[0, s, r:r + 1, :] - y0.astype(jnp.float32)
            sy = jnp.clip(sy, 0.0, BAND - 1.0)             # band coverage
            wx = jnp.maximum(1.0 - jnp.abs(xs - sx), 0.0)  # [W_s, W_t]
            t = jnp.dot(flat, wx, preferred_element_type=jnp.float32)
            t = t.reshape(7, BAND, W_t)
            wy = jnp.maximum(1.0 - jnp.abs(ys - sy), 0.0)  # [BAND, W_t]
            rows.append(jnp.sum(t * wy[None], axis=1))     # [7, W_t]
        w7 = jnp.stack(rows, axis=1)                       # [7, RT, W_t]
        cur = (w7[0:3], w7[3], w7[4:7])

        if prev is not None:
            diff = cur[2] - prev[2]
            dist = jnp.sqrt(jnp.sum(diff * diff, axis=0))
            t_acc, acc_rgb, acc_d, acc_w = composite_step(
                prev, dist, (t_acc, acc_rgb, acc_d, acc_w))
        prev = cur

    dist = jnp.full((RT, W_t), 1e3, jnp.float32)  # last plane: far distance
    t_acc, acc_rgb, acc_d, acc_w = composite_step(
        prev, dist, (t_acc, acc_rgb, acc_d, acc_w))

    rgb_out[0] = acc_rgb
    if is_bg_depth_inf:
        depth_out[0, 0] = acc_d + (1.0 - acc_w) * 1000.0
    else:
        depth_out[0, 0] = acc_d / (acc_w + 1e-5)


@functools.partial(jax.jit, static_argnames=("band", "rows_per_block",
                                             "is_bg_depth_inf", "interpret"))
def fused_plane_render(vol_q: jnp.ndarray,
                       scales: Optional[jnp.ndarray],
                       xyz_tgt: jnp.ndarray,
                       coords_x: jnp.ndarray,
                       coords_y: jnp.ndarray,
                       band: int = 16,
                       rows_per_block: int = 8,
                       is_bg_depth_inf: bool = False,
                       interpret: bool = False
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The megakernel: cached planes -> composited target view, one pass.

    Args:
      vol_q: [B,S,4,H_s,W_s] rgb+sigma planes in CACHE form (f32/bf16/int8)
      scales: [B,S,4,1,1] f32 int8 dequant scales, or None (f32/bf16)
      xyz_tgt: [B,S,3,H_s,W_s] f32 per-plane target-frame coordinates
        (warped alongside the planes, exactly as the 7-channel xla volume)
      coords_x, coords_y: [B,S,H_t,W_t] source pixel coords per plane
    Returns: (rgb [B,3,H_t,W_t] f32, depth [B,1,H_t,W_t] f32)

    Caller contract: coords must satisfy fused_domain_ok (the guarded
    wrapper below enforces it at runtime with the XLA fallback).
    """
    B, S, _, H_s, W_s0 = vol_q.shape
    _, _, H_t, W_t = coords_x.shape
    RT = rows_per_block
    assert H_t % RT == 0, (H_t, RT)
    NB = H_t // RT
    align = sublane_align(vol_q.dtype)
    band = min(band, H_s)

    xc = jnp.clip(coords_x, 0.0, W_s0 - 1.0).astype(jnp.float32)
    yc = jnp.clip(coords_y, 0.0, H_s - 1.0).astype(jnp.float32)

    # Mosaic alignment at the CACHE dtype's tile (module docstring): pad
    # the source rows/lanes, never the values — padded columns/rows sit
    # >= 1 px outside the clipped coord range, so their tent weights are
    # exactly zero
    band, pad_h, pad_w = fused_band_geometry(band, H_s, W_s0, align)
    if pad_h or pad_w:
        pad = ((0, 0), (0, 0), (0, 0), (0, pad_h), (0, pad_w))
        vol_q = jnp.pad(vol_q, pad)
        xyz_tgt = jnp.pad(xyz_tgt, pad)
    H_pad, W_s = vol_q.shape[3], vol_q.shape[4]

    # band starts per (view, plane, row-block), floored to the cache tile
    # (kernels/warp.py band_start + alignment rule, at `align` not 8)
    yflat = yc.reshape(B * S, NB, RT * W_t)
    y0 = jnp.floor(jnp.min(yflat, axis=2)).astype(jnp.int32)
    y0 = jnp.clip(y0, 0, max(H_pad - band, 0))
    y0 = (y0 // align) * align                             # [B*S, NB]

    dequant = scales is not None
    scale_2d = (scales.reshape(B * S, 4).astype(jnp.float32) if dequant
                else jnp.ones((B * S, 4), jnp.float32))

    grid = (B, NB)
    kernel = functools.partial(_fused_kernel, S, band, RT, W_s, dequant,
                               is_bg_depth_inf, align)

    coord_spec = pl.BlockSpec((1, S, RT, W_t), lambda b, r: (b, 0, r, 0),
                              memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((B * S, NB), lambda b, r: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((B * S, 4), lambda b, r: (0, 0),
                         memory_space=pltpu.SMEM),
            coord_spec,
            coord_spec,
            pl.BlockSpec((B, S, 4, H_pad, W_s), lambda b, r: (0, 0, 0, 0, 0),
                         memory_space=pl.ANY),  # HBM-resident; banded DMA
            pl.BlockSpec((B, S, 3, H_pad, W_s), lambda b, r: (0, 0, 0, 0, 0),
                         memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 3, RT, W_t), lambda b, r: (b, 0, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, RT, W_t), lambda b, r: (b, 0, r, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 3, H_t, W_t), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, H_t, W_t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((4, band, W_s), vol_q.dtype),
            pltpu.VMEM((3, band, W_s), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
        name="render_fused_warp_composite",
        interpret=interpret,
    )(y0, scale_2d, xc, yc, vol_q, xyz_tgt.astype(jnp.float32))


def xla_reference_render(vol_q: jnp.ndarray,
                         scales: Optional[jnp.ndarray],
                         xyz_tgt: jnp.ndarray,
                         coords_x: jnp.ndarray,
                         coords_y: jnp.ndarray,
                         is_bg_depth_inf: bool = False
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The graph the megakernel replaces, op-for-op the `backend="xla"`
    serve path: dequant -> 7-channel gather warp -> z-mask -> sigma
    composite. Triple duty: the guarded wrapper's runtime fallback branch,
    the custom-VJP backward graph, and the parity-test reference."""
    from mine_tpu.ops import rendering
    from mine_tpu.ops.warp import bilinear_sample

    B, S, _, H, W = vol_q.shape
    _, _, H_t, W_t = coords_x.shape
    x = vol_q.astype(jnp.float32)
    if scales is not None:
        x = x * scales  # fused dequant, serve/engine.py _render_impl
    volume = jnp.concatenate([x, xyz_tgt.astype(jnp.float32)], axis=2)
    warped = bilinear_sample(volume.reshape(B * S, 7, H, W),
                             coords_x.reshape(B * S, H_t, W_t),
                             coords_y.reshape(B * S, H_t, W_t))
    warped = warped.reshape(B, S, 7, H_t, W_t)
    tgt_rgb = warped[:, :, 0:3]
    tgt_sigma = warped[:, :, 3:4]
    tgt_xyz = warped[:, :, 4:7]
    tgt_z = tgt_xyz[:, :, 2:3]
    tgt_sigma = jnp.where(tgt_z >= 0.0, tgt_sigma, 0.0)
    rgb, depth, _, _ = rendering.render(tgt_rgb, tgt_sigma, tgt_xyz,
                                        use_alpha=False,
                                        is_bg_depth_inf=is_bg_depth_inf)
    return rgb, depth


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def fused_plane_render_diff(vol_q, scales, xyz_tgt, coords_x, coords_y,
                            band: int, rows_per_block: int,
                            is_bg_depth_inf: bool, interpret: bool):
    """Trainable megakernel (kernels/warp_sep.py custom-VJP pattern): the
    forward runs the fused Pallas kernel; the backward differentiates the
    XLA-equivalent graph (`xla_reference_render`) — the fused op is one
    kernel on the way forward, and autodiff-exact on the way back. Coords
    get zero cotangents (non-learnable, matching every warp VJP here);
    scales are dequant constants (zero cotangent)."""
    return fused_plane_render(vol_q, scales, xyz_tgt, coords_x, coords_y,
                              band=band, rows_per_block=rows_per_block,
                              is_bg_depth_inf=is_bg_depth_inf,
                              interpret=interpret)


def _fused_diff_fwd(vol_q, scales, xyz_tgt, coords_x, coords_y,
                    band, rows_per_block, is_bg_depth_inf, interpret):
    out = fused_plane_render_diff(vol_q, scales, xyz_tgt, coords_x,
                                  coords_y, band, rows_per_block,
                                  is_bg_depth_inf, interpret)
    return out, (vol_q, scales, xyz_tgt, coords_x, coords_y)


def _fused_diff_bwd(band, rows_per_block, is_bg_depth_inf, interpret,
                    residuals, g):
    vol_q, scales, xyz_tgt, coords_x, coords_y = residuals

    def ref(v, x):
        return xla_reference_render(v, scales, x, coords_x, coords_y,
                                    is_bg_depth_inf)

    _, vjp = jax.vjp(ref, vol_q.astype(jnp.float32),
                     xyz_tgt.astype(jnp.float32))
    d_vol, d_xyz = vjp(g)
    d_scales = None if scales is None else jnp.zeros_like(scales)
    return (d_vol.astype(vol_q.dtype), d_scales,
            d_xyz.astype(xyz_tgt.dtype),
            jnp.zeros_like(coords_x), jnp.zeros_like(coords_y))


fused_plane_render_diff.defvjp(_fused_diff_fwd, _fused_diff_bwd)


def fused_plane_render_guarded(vol_q: jnp.ndarray,
                               scales: Optional[jnp.ndarray],
                               xyz_tgt: jnp.ndarray,
                               coords_x: jnp.ndarray,
                               coords_y: jnp.ndarray,
                               band: int = 16,
                               rows_per_block: int = 8,
                               is_bg_depth_inf: bool = False,
                               interpret: bool = False):
    """Guarded megakernel (the house lax.cond pattern, kernels/warp_sep.py):
    in-domain poses run the one-pass kernel, everything else takes the XLA
    dequant+gather+composite — same values, reported via the returned
    scalar `ok` so warp_fallback_frac sees it.

    Returns (rgb, depth, ok[bool scalar])."""
    H_t = coords_x.shape[2]
    if H_t % rows_per_block:
        # statically out of domain — lax.cond traces BOTH branches, so the
        # kernel (which requires the row-block tiling) must not be staged
        rgb, depth = xla_reference_render(vol_q, scales, xyz_tgt, coords_x,
                                          coords_y, is_bg_depth_inf)
        return rgb, depth, jnp.zeros((), jnp.bool_)
    ok = fused_domain_ok(vol_q.shape, vol_q.dtype, coords_y, band,
                         rows_per_block)

    def fast(v, sc, x, a, b):
        return fused_plane_render_diff(v, sc, x, a, b, band,
                                       rows_per_block, is_bg_depth_inf,
                                       interpret)

    def slow(v, sc, x, a, b):
        return xla_reference_render(v, sc, x, a, b, is_bg_depth_inf)

    rgb, depth = jax.lax.cond(ok, fast, slow, vol_q, scales, xyz_tgt,
                              coords_x, coords_y)
    return rgb, depth, ok
