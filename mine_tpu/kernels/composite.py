"""Pallas TPU kernels: fused MPI volume compositing.

The compositing math (operations/mpi_rendering.py:42-82 in the reference) is
HBM-bound: XLA materializes per-plane intermediates (plane distances,
transparency, the exclusive cumprod, weights, weighted rgb/depth) as
[B,S,1,H,W] HBM tensors. These kernels stream the plane volume through VMEM
once per spatial tile, carrying the accumulated transparency and the three
output accumulators in registers/VMEM — one HBM read per input element, one
write per output element, nothing else.

Two kernels:
  * fused_volume_render: target-view composite (optionally zeroing density
    behind the camera, mpi_rendering.py:233-235) -> (rgb, depth)
  * fused_src_render_blend: source-view composite FUSED with the reference's
    src rgb blending + re-composite (synthesis_task.py:260-275, two full
    passes upstream) -> (rgb, depth, blended rgb volume) in a single pass

Both are forward-only (inference/eval); training uses the XLA path, which
autodiffs. Numerical equivalence with the XLA path is test-gated
(tests/test_kernels.py), and `interpret=True` runs them on CPU.

Layout: [B, S, C, H, W] with W on the 128-lane axis and H on sublanes; the
grid walks (batch, H-tiles, W-tiles) and the plane loop is statically
unrolled. Block planning is centralized in `_plan_blocks`: rows pad to the
8-row sublane tile, W tiles over lane-aligned divisors when the minimum
H-tile exceeds the VMEM budget, and lane-UNALIGNED widths that need
W-tiling get zero column padding first (all exact — pixels are
independent across H and W; the transparency chain reduces over S only).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pick_tile_h(H: int, W: int, S: int,
                 budget: int = 4 * 1024 * 1024,
                 rows_per_plane: int = 7) -> int:
    """Largest H-tile (multiple of 8 or == H) keeping the block under budget.

    rows_per_plane = plane-sized f32 rows resident per spatial row (inputs +
    outputs + scratch); the backward kernel passes a larger value.

    Callers never pass an H with no multiple-of-8 divisor: every kernel
    wrapper in this file pads rows to the next multiple of 8 first
    (padded_rows_call), so a small legal tile always exists. The `H`
    fallthrough below is only reachable if this function is reused on an
    unpadded shape."""
    per_row = S * rows_per_plane * W * 4
    fit = min(max(1, budget // max(per_row, 1)), H)
    # Mosaic-legal tiles: divisors of H that are multiples of 8 (the f32
    # sublane tile), or H itself. Largest legal tile within budget; if the
    # budget admits none, the smallest legal tile — over budget beats an
    # illegal block (~12 MB double-buffered at the worst LLFF bwd shape,
    # within the ~16 MB/core VMEM; validated on-device).
    legal = [d for d in range(8, H + 1, 8) if H % d == 0]
    in_budget = [d for d in legal if d <= fit]
    if in_budget:
        return max(in_budget)
    return min(legal) if legal else H


def _pick_tiles(H: int, W: int, S: int,
                budget: int = 4 * 1024 * 1024,
                rows_per_plane: int = 7) -> tuple:
    """(TH, TW): H-tile as _pick_tile_h; if even the minimum H-tile blows
    the budget, ALSO tile W over lane-aligned (128-multiple) divisors.

    Needed on silicon (round-4 window): at the reference-exact 512-wide
    scale 0 the backward composite's minimum 8-row block is 16.09M scoped
    VMEM — 88K over the 16M limit. Pixels are independent across W (the
    transparency chain reduces over S), so W-tiling is exact."""
    TH = _pick_tile_h(H, W, S, budget, rows_per_plane)
    if TH * S * rows_per_plane * W * 4 <= budget or W % 128:
        return TH, W  # fits, or no lane-aligned divisor exists
    legal_w = [d for d in range(128, W + 1, 128) if W % d == 0]
    per_col = TH * S * rows_per_plane * 4
    in_budget = [d for d in legal_w if d * per_col <= budget]
    if in_budget:
        return TH, max(in_budget)
    return TH, min(legal_w)


def pallas_tileable(H: int) -> bool:
    """True when H admits a Mosaic-legal tile — a divisor that is a multiple
    of 8, which exists iff 8 | H. Other heights (e.g. H=756 full-res eval)
    are handled INSIDE every kernel wrapper here by zero-padding rows to
    the next multiple of 8 and slicing the outputs — exact, because the
    composite reduces over S with pixels independent across H."""
    return H % 8 == 0


def pad_rows(x: jnp.ndarray, pad: int) -> jnp.ndarray:
    """Zero-pad the H axis (second-to-last) of any (..., H, W) tensor."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)])


def _plan_blocks(H: int, W: int, S: int,
                 budget: int = 4 * 1024 * 1024,
                 rows_per_plane: int = 7) -> tuple:
    """(TH, TW, cpad): THE block plan, one call per wrapper so the column
    pad and the tile choice can never desynchronize (they share budget and
    rows_per_plane by construction).

    cpad > 0 means: re-enter the wrapper with cpad zero columns appended
    (lane-UNALIGNED width that needs W-tiling — e.g. the S=64
    coarse-to-fine 192-wide scale 1, a round-4 on-silicon scoped-VMEM
    OOM); TH/TW are then for the PADDED width. Zero columns carry sigma=0
    (weight 0) / zero cotangents, pixels are independent across W — exact
    after slicing."""
    if W % 128 and _pick_tiles(H, W, S, budget, rows_per_plane)[0] \
            * S * rows_per_plane * W * 4 > budget:
        return (*_pick_tiles(H, W + (-W) % 128, S, budget, rows_per_plane),
                (-W) % 128)
    return (*_pick_tiles(H, W, S, budget, rows_per_plane), 0)


def _padded_axis_call(fn, arrs, pad: int, real: int, axis: int, **kw):
    """THE pad-call-slice rule: zero-pad `axis` of each (..., H, W) arg,
    call fn, slice every output back to `real`. Exact because the
    composite kernels reduce over S with pixels independent across H and
    W (padded rows/columns: sigma=0 -> weight 0; zero cotangents -> zero
    grads)."""
    def pad_one(a):
        w = [(0, 0)] * a.ndim
        w[axis] = (0, pad)
        return jnp.pad(a, w)

    out = fn(*(pad_one(a) for a in arrs), **kw)
    index = (Ellipsis, slice(None, real), slice(None)) if axis == -2 \
        else (Ellipsis, slice(None, real))
    if isinstance(out, tuple):
        return tuple(o[index] for o in out)
    return out[index]


def padded_cols_call(fn, arrs, pad: int, real_W: int, **kw):
    """Column form of the pad-call-slice rule."""
    return _padded_axis_call(fn, arrs, pad, real_W, -1, **kw)


def padded_rows_call(fn, arrs, pad: int, real_H: int, **kw):
    """Row form of the pad-call-slice rule (_padded_axis_call)."""
    return _padded_axis_call(fn, arrs, pad, real_H, -2, **kw)


def _tgt_kernel(S: int, z_mask: bool, is_bg_depth_inf: bool,
                rgb_ref, sigma_ref, xyz_ref, rgb_out, depth_out):
    TH, W = rgb_ref.shape[3], rgb_ref.shape[4]
    t_acc = jnp.ones((TH, W), jnp.float32)
    acc_rgb = jnp.zeros((3, TH, W), jnp.float32)
    acc_d = jnp.zeros((TH, W), jnp.float32)
    acc_w = jnp.zeros((TH, W), jnp.float32)

    for s in range(S):
        xyz_s = xyz_ref[0, s]          # [3, TH, W]
        if s < S - 1:
            diff = xyz_ref[0, s + 1] - xyz_s
            dist = jnp.sqrt(jnp.sum(diff * diff, axis=0))
        else:
            dist = jnp.full((TH, W), 1e3, jnp.float32)
        sig = sigma_ref[0, s, 0]
        if z_mask:
            sig = jnp.where(xyz_s[2] >= 0.0, sig, 0.0)
        trans = jnp.exp(-sig * dist)
        w = t_acc * (1.0 - trans)
        acc_rgb = acc_rgb + w[None] * rgb_ref[0, s]
        acc_d = acc_d + w * xyz_s[2]
        acc_w = acc_w + w
        t_acc = t_acc * (trans + 1e-6)

    rgb_out[0] = acc_rgb
    if is_bg_depth_inf:
        depth_out[0, 0] = acc_d + (1.0 - acc_w) * 1000.0
    else:
        depth_out[0, 0] = acc_d / (acc_w + 1e-5)


@functools.partial(jax.jit, static_argnames=("z_mask", "is_bg_depth_inf",
                                             "interpret"))
def fused_volume_render(rgb_BS3HW: jnp.ndarray,
                        sigma_BS1HW: jnp.ndarray,
                        xyz_BS3HW: jnp.ndarray,
                        z_mask: bool = False,
                        is_bg_depth_inf: bool = False,
                        interpret: bool = False
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused equivalent of rendering.plane_volume_rendering (+ optional
    behind-camera masking) returning (rgb [B,3,H,W], depth [B,1,H,W]).
    Any H is accepted (rows padded to a Mosaic-legal multiple of 8)."""
    B, S, _, real_H, W = rgb_BS3HW.shape
    TH, TW, cpad = _plan_blocks(real_H + (-real_H) % 8, W, S)
    if cpad:
        return padded_cols_call(
            fused_volume_render, (rgb_BS3HW, sigma_BS1HW, xyz_BS3HW),
            cpad, W, z_mask=z_mask, is_bg_depth_inf=is_bg_depth_inf,
            interpret=interpret)
    pad = (-real_H) % 8
    if pad:
        return padded_rows_call(
            fused_volume_render, (rgb_BS3HW, sigma_BS1HW, xyz_BS3HW),
            pad, real_H, z_mask=z_mask, is_bg_depth_inf=is_bg_depth_inf,
            interpret=interpret)
    H = real_H
    grid = (B, H // TH, W // TW)

    def vol_spec(C):
        return pl.BlockSpec((1, S, C, TH, TW),
                            lambda b, h, w: (b, 0, 0, h, w),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(_tgt_kernel, S, z_mask, is_bg_depth_inf),
        grid=grid,
        in_specs=[vol_spec(3), vol_spec(1), vol_spec(3)],
        out_specs=[
            pl.BlockSpec((1, 3, TH, TW), lambda b, h, w: (b, 0, h, w),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, TH, TW), lambda b, h, w: (b, 0, h, w),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 3, H, W), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, H, W), jnp.float32),
        ],
        name="composite_volume_render_fwd",
        interpret=interpret,
    )(rgb_BS3HW.astype(jnp.float32), sigma_BS1HW.astype(jnp.float32),
      xyz_BS3HW.astype(jnp.float32))


def _src_blend_kernel(S: int, is_bg_depth_inf: bool,
                      rgb_ref, sigma_ref, xyz_ref, src_ref,
                      rgb_out, depth_out, blended_out):
    TH, W = rgb_ref.shape[3], rgb_ref.shape[4]
    src = src_ref[0]  # [3, TH, W]
    t_acc = jnp.ones((TH, W), jnp.float32)
    acc_rgb = jnp.zeros((3, TH, W), jnp.float32)
    acc_d = jnp.zeros((TH, W), jnp.float32)
    acc_w = jnp.zeros((TH, W), jnp.float32)

    for s in range(S):
        xyz_s = xyz_ref[0, s]
        if s < S - 1:
            diff = xyz_ref[0, s + 1] - xyz_s
            dist = jnp.sqrt(jnp.sum(diff * diff, axis=0))
        else:
            dist = jnp.full((TH, W), 1e3, jnp.float32)
        sig = sigma_ref[0, s, 0]
        trans = jnp.exp(-sig * dist)
        w = t_acc * (1.0 - trans)
        # blend_weights for plane s is the exclusive accumulated transparency
        # (synthesis_task.py:267-268): planes visible from the camera copy the
        # real source pixels
        blended = t_acc[None] * src + (1.0 - t_acc[None]) * rgb_ref[0, s]
        blended_out[0, s] = blended
        acc_rgb = acc_rgb + w[None] * blended
        acc_d = acc_d + w * xyz_s[2]
        acc_w = acc_w + w
        t_acc = t_acc * (trans + 1e-6)

    rgb_out[0] = acc_rgb
    if is_bg_depth_inf:
        depth_out[0, 0] = acc_d + (1.0 - acc_w) * 1000.0
    else:
        depth_out[0, 0] = acc_d / (acc_w + 1e-5)


@functools.partial(jax.jit, static_argnames=("is_bg_depth_inf", "interpret"))
def fused_src_render_blend(rgb_BS3HW: jnp.ndarray,
                           sigma_BS1HW: jnp.ndarray,
                           xyz_BS3HW: jnp.ndarray,
                           src_img_B3HW: jnp.ndarray,
                           is_bg_depth_inf: bool = False,
                           interpret: bool = False):
    """Source-view composite + rgb blending + re-composite in one pass.

    Equivalent to rendering.render + the blending block of the reference
    (synthesis_task.py:260-275). Returns (rgb [B,3,H,W], depth [B,1,H,W],
    blended mpi rgb [B,S,3,H,W] — the volume the novel-view warp consumes).
    Any H is accepted (rows padded to a Mosaic-legal multiple of 8).
    """
    B, S, _, real_H, W = rgb_BS3HW.shape
    TH, TW, cpad = _plan_blocks(real_H + (-real_H) % 8, W, S,
                                rows_per_plane=10)  # +3: blended out vol
    if cpad:
        return padded_cols_call(
            fused_src_render_blend,
            (rgb_BS3HW, sigma_BS1HW, xyz_BS3HW, src_img_B3HW),
            cpad, W, is_bg_depth_inf=is_bg_depth_inf, interpret=interpret)
    pad = (-real_H) % 8
    if pad:
        return padded_rows_call(
            fused_src_render_blend,
            (rgb_BS3HW, sigma_BS1HW, xyz_BS3HW, src_img_B3HW),
            pad, real_H, is_bg_depth_inf=is_bg_depth_inf,
            interpret=interpret)
    H = real_H
    grid = (B, H // TH, W // TW)

    def vol_spec(C):
        return pl.BlockSpec((1, S, C, TH, TW),
                            lambda b, h, w: (b, 0, 0, h, w),
                            memory_space=pltpu.VMEM)

    img_spec = pl.BlockSpec((1, 3, TH, TW), lambda b, h, w: (b, 0, h, w),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(_src_blend_kernel, S, is_bg_depth_inf),
        grid=grid,
        in_specs=[vol_spec(3), vol_spec(1), vol_spec(3), img_spec],
        out_specs=[
            img_spec,
            pl.BlockSpec((1, 1, TH, TW), lambda b, h, w: (b, 0, h, w),
                         memory_space=pltpu.VMEM),
            vol_spec(3),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 3, H, W), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, H, W), jnp.float32),
            jax.ShapeDtypeStruct((B, S, 3, H, W), jnp.float32),
        ],
        name="composite_src_render_blend_fwd",
        interpret=interpret,
    )(rgb_BS3HW.astype(jnp.float32), sigma_BS1HW.astype(jnp.float32),
      xyz_BS3HW.astype(jnp.float32), src_img_B3HW.astype(jnp.float32))
