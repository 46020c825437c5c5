"""Custom VJP for the fused MPI composite — Pallas forward AND backward.

Makes the fused composite usable in training: the forward is
kernels.composite.fused_volume_render; the backward below recomputes the
per-plane transparency chain in one up-pass (cheap VPU math, nothing
materialized in HBM) and walks the planes in reverse with a suffix
accumulator for the cumulative-product chain rule:

  w_s = T_s * (1 - trans_s),  T_s = prod_{j<s}(trans_j + 1e-6)
  dL/dtrans_s = -T_s * dL/dw_s + A_s / (trans_s + 1e-6),
  A_s = sum_{k>s} dL/dw_k * w_k   (suffix, built during the reverse walk)

then through trans = exp(-sigma*dist) to sigma and, via the plane-distance
norm, to xyz. Gradient correctness is test-gated against jax.grad of the XLA
path (tests/test_composite_vjp.py) for both depth modes and the z-mask.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mine_tpu.kernels.composite import (_plan_blocks, fused_volume_render,
                                        padded_cols_call, padded_rows_call)


def _plan_blocks_bwd(H: int, W: int, S: int):
    """Backward block plan: inputs+grads+outputs+scratch ~ 19 plane-sized
    rows. W-tiling kicks in at wide shapes — the 512-wide reference-exact
    scale 0 was 88K over the 16M scoped-VMEM limit at the minimum 8-row
    tile (round-4 on-silicon OOM; _plan_blocks docstring)."""
    return _plan_blocks(H, W, S, budget=5 * 1024 * 1024, rows_per_plane=19)


def _bwd_kernel(S: int, z_mask: bool, is_bg_depth_inf: bool,
                rgb_ref, sigma_ref, xyz_ref, g_rgb_ref, g_depth_ref,
                d_rgb_ref, d_sigma_ref, d_xyz_ref,
                trans_buf, tacc_buf):
    TH, W = rgb_ref.shape[3], rgb_ref.shape[4]

    # ---- pass 1 (up): recompute transparency chain + output accumulators ----
    t_acc = jnp.ones((TH, W), jnp.float32)
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
        if z_mask:
            sig = jnp.where(xyz_s[2] >= 0.0, sig, 0.0)
        trans = jnp.exp(-sig * dist)
        w = t_acc * (1.0 - trans)
        trans_buf[s] = trans
        tacc_buf[s] = t_acc
        acc_d = acc_d + w * xyz_s[2]
        acc_w = acc_w + w
        t_acc = t_acc * (trans + 1e-6)

    g_rgb = g_rgb_ref[0]        # [3, TH, W]
    g_depth = g_depth_ref[0, 0]  # [TH, W]
    if is_bg_depth_inf:
        g_acc_d = g_depth
        g_acc_w = -1000.0 * g_depth
    else:
        denom = acc_w + 1e-5
        g_acc_d = g_depth / denom
        g_acc_w = -g_depth * acc_d / (denom * denom)

    # ---- pass 2 (down): reverse walk with the suffix accumulator ----
    # zero-init the xyz grad output (accumulated across two planes each)
    for s in range(S):
        d_xyz_ref[0, s] = jnp.zeros((3, TH, W), jnp.float32)

    A = jnp.zeros((TH, W), jnp.float32)
    for s in range(S - 1, -1, -1):
        xyz_s = xyz_ref[0, s]
        trans = trans_buf[s]
        t_acc_s = tacc_buf[s]
        w = t_acc_s * (1.0 - trans)  # recomputed: cheaper than a 3rd scratch
        z_s = xyz_s[2]

        dldw = (jnp.sum(g_rgb * rgb_ref[0, s], axis=0)
                + g_acc_d * z_s + g_acc_w)

        d_rgb_ref[0, s] = w[None] * g_rgb
        # direct depth-accumulator contribution to z
        d_z_direct = w * g_acc_d

        dldtrans = -t_acc_s * dldw + A / (trans + 1e-6)
        A = A + dldw * w

        if s < S - 1:
            diff = xyz_ref[0, s + 1] - xyz_s
            dist = jnp.sqrt(jnp.sum(diff * diff, axis=0))
            sig = sigma_ref[0, s, 0]
            if z_mask:
                sig = jnp.where(z_s >= 0.0, sig, 0.0)
            d_sig = dldtrans * (-dist * trans)
            d_dist = dldtrans * (-sig * trans)
            # dist -> xyz: d(dist)/d(diff) = diff / dist
            unit = diff / jnp.maximum(dist, 1e-12)[None]
            d_xyz_ref[0, s + 1] = d_xyz_ref[0, s + 1] + d_dist[None] * unit
            grad_self = -d_dist[None] * unit
        else:
            # last plane: dist is the 1e3 constant
            d_sig = dldtrans * (-1e3 * trans)
            grad_self = jnp.zeros((3, TH, W), jnp.float32)

        if z_mask:
            d_sig = jnp.where(z_s >= 0.0, d_sig, 0.0)
        d_sigma_ref[0, s, 0] = d_sig

        zero = jnp.zeros((TH, W), jnp.float32)
        grad_self = grad_self + jnp.stack([zero, zero, d_z_direct], axis=0)
        d_xyz_ref[0, s] = d_xyz_ref[0, s] + grad_self


@functools.partial(jax.jit, static_argnames=("z_mask", "is_bg_depth_inf",
                                             "interpret"))
def _composite_bwd(rgb, sigma, xyz, g_rgb, g_depth,
                   z_mask: bool, is_bg_depth_inf: bool,
                   interpret: bool = False):
    B, S, _, real_H, W = rgb.shape
    TH, TW, cpad = _plan_blocks_bwd(real_H + (-real_H) % 8, W, S)
    if cpad:
        # zero-padded columns carry zero cotangents -> zero grads there
        return padded_cols_call(
            _composite_bwd, (rgb, sigma, xyz, g_rgb, g_depth), cpad, W,
            z_mask=z_mask, is_bg_depth_inf=is_bg_depth_inf,
            interpret=interpret)
    pad = (-real_H) % 8
    if pad:
        # padded rows carry sigma=0 and zero cotangents: their grads are 0
        # and the real rows' grads are untouched (pixels independent over H)
        return padded_rows_call(
            _composite_bwd, (rgb, sigma, xyz, g_rgb, g_depth), pad, real_H,
            z_mask=z_mask, is_bg_depth_inf=is_bg_depth_inf,
            interpret=interpret)
    H = real_H
    grid = (B, H // TH, W // TW)

    def vol_spec(C):
        return pl.BlockSpec((1, S, C, TH, TW),
                            lambda b, h, w: (b, 0, 0, h, w),
                            memory_space=pltpu.VMEM)

    def img_spec(C):
        return pl.BlockSpec((1, C, TH, TW), lambda b, h, w: (b, 0, h, w),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(_bwd_kernel, S, z_mask, is_bg_depth_inf),
        grid=grid,
        in_specs=[vol_spec(3), vol_spec(1), vol_spec(3),
                  img_spec(3), img_spec(1)],
        out_specs=[vol_spec(3), vol_spec(1), vol_spec(3)],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, 3, H, W), jnp.float32),
            jax.ShapeDtypeStruct((B, S, 1, H, W), jnp.float32),
            jax.ShapeDtypeStruct((B, S, 3, H, W), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((S, TH, TW), jnp.float32),
            pltpu.VMEM((S, TH, TW), jnp.float32),
        ],
        name="composite_volume_render_bwd",
        interpret=interpret,
    )(rgb.astype(jnp.float32), sigma.astype(jnp.float32),
      xyz.astype(jnp.float32), g_rgb.astype(jnp.float32),
      g_depth.astype(jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_volume_render_diff(rgb, sigma, xyz,
                             z_mask: bool = False,
                             is_bg_depth_inf: bool = False,
                             interpret: bool = False
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Differentiable fused composite: Pallas forward + Pallas backward.

    Same contract as kernels.composite.fused_volume_render; gradients flow to
    rgb, sigma, and xyz (the full training chain — xyz carries disparity and
    pose geometry downstream of stop_gradients, matching the XLA path)."""
    return fused_volume_render(rgb, sigma, xyz, z_mask=z_mask,
                               is_bg_depth_inf=is_bg_depth_inf,
                               interpret=interpret)


def _fwd(rgb, sigma, xyz, z_mask, is_bg_depth_inf, interpret):
    out = fused_volume_render(rgb, sigma, xyz, z_mask=z_mask,
                              is_bg_depth_inf=is_bg_depth_inf,
                              interpret=interpret)
    return out, (rgb, sigma, xyz)


def _bwd(z_mask, is_bg_depth_inf, interpret, residuals, grads):
    rgb, sigma, xyz = residuals
    g_rgb, g_depth = grads
    d_rgb, d_sigma, d_xyz = _composite_bwd(
        rgb, sigma, xyz, g_rgb, g_depth,
        z_mask=z_mask, is_bg_depth_inf=is_bg_depth_inf, interpret=interpret)
    return d_rgb, d_sigma, d_xyz


fused_volume_render_diff.defvjp(_fwd, _bwd)
