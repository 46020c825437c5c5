"""Homography warping of the MPI plane volume.

Replaces the reference's HomographySample (homography_sampler.py:10-141),
whose hot op is `F.grid_sample(padding_mode='border', align_corners=False)`
over a B*S x 7 x H x W volume (rgb, sigma and the plane points; here the
volume is the four learned channels, and the points are a formula at the
same coordinates: ops/rendering.render_tgt_rgb_depth). On TPU this is a
gather; the XLA path below is the reference implementation, designed so a
Pallas kernel with the same contract can slot in as the fused fast path.

Sampling semantics (must match for checkpoint parity — SURVEY.md section 7
"hard parts" #1): the reference normalizes pixel coords p to grid
g = (p+0.5)/(0.5*size) - 1 (homography_sampler.py:136-137) and then
grid_sample with align_corners=False maps g back to pixels as
(g+1)*size/2 - 0.5 == p. Net effect: bilinear sampling at continuous pixel
coordinates with border clamping. We implement that directly, skipping the
[-1,1] round trip.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from mine_tpu import geometry


def bilinear_sample(src: jnp.ndarray,
                    coords_x: jnp.ndarray,
                    coords_y: jnp.ndarray,
                    gather_dtype=None) -> jnp.ndarray:
    """Bilinear sample with border padding at continuous pixel coords.

    Equivalent to torch grid_sample(border, align_corners=False) after the
    reference's grid normalization (see module docstring).

    Args:
      src: [B, C, H, W]
      coords_x, coords_y: [B, Ho, Wo] sample locations in src pixel coords
      gather_dtype: optional storage dtype for the gathered FORWARD values
        (jnp.bfloat16 halves the forward HBM read of the hot
        B*S x 4 x H x W volume at ~2^-8 relative value rounding; the lerp
        runs in float32 and the BACKWARD scatter-add accumulates in float32
        via a custom VJP — a bf16 scatter would drop contributions below
        ~2^-8 of the running sum wherever many target pixels hit the same
        source texel. The bf16 path returns zero coordinate cotangents,
        matching kernels/warp_vjp.py; every training caller stop-gradients
        coords anyway.)
    Returns: [B, C, Ho, Wo] float32
    """
    # float32 (or None) is the identity storage dtype -> plain autodiff path;
    # any reduced dtype ALWAYS routes through the f32-accumulating custom VJP
    # (even when src already arrives reduced — the plain path's backward
    # would scatter-accumulate in the reduced dtype).
    if gather_dtype is not None and jnp.dtype(gather_dtype) != jnp.float32:
        return _bilinear_sample_cast(src.astype(jnp.float32), coords_x,
                                     coords_y, jnp.dtype(gather_dtype).name)
    return _lerp_gather(src, coords_x, coords_y)


def _lerp_gather(src: jnp.ndarray, coords_x: jnp.ndarray,
                 coords_y: jnp.ndarray) -> jnp.ndarray:
    """Autodiffable core: gather in src's dtype, lerp in float32."""
    B, C, H, W = src.shape
    # Border padding == clamp the sampling location into the pixel-center box.
    x = jnp.clip(coords_x, 0.0, W - 1.0)
    y = jnp.clip(coords_y, 0.0, H - 1.0)

    x0 = jnp.floor(x)
    y0 = jnp.floor(y)
    tx = x - x0
    ty = y - y0

    x0i = x0.astype(jnp.int32)
    y0i = y0.astype(jnp.int32)
    x1i = jnp.minimum(x0i + 1, W - 1)
    y1i = jnp.minimum(y0i + 1, H - 1)

    def gather_one(img_chw, yi, xi):
        # img_chw [C,H,W]; yi/xi [Ho,Wo] -> [C,Ho,Wo]
        return img_chw[:, yi, xi]

    g = jax.vmap(gather_one)
    v00 = g(src, y0i, x0i)
    v01 = g(src, y0i, x1i)
    v10 = g(src, y1i, x0i)
    v11 = g(src, y1i, x1i)

    tx = tx[:, None, :, :]
    ty = ty[:, None, :, :]
    if src.dtype != jnp.float32:  # lerp in f32 regardless of storage dtype
        v00, v01, v10, v11 = (v.astype(jnp.float32)
                              for v in (v00, v01, v10, v11))
    top = v00 * (1.0 - tx) + v01 * tx
    bot = v10 * (1.0 - tx) + v11 * tx
    return top * (1.0 - ty) + bot * ty


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _bilinear_sample_cast(src, coords_x, coords_y, gather_dtype: str):
    """bf16-storage forward, f32-accumulating backward (see bilinear_sample)."""
    return _lerp_gather(src.astype(gather_dtype), coords_x, coords_y)


def _bsc_fwd(src, coords_x, coords_y, gather_dtype):
    out = _bilinear_sample_cast(src, coords_x, coords_y, gather_dtype)
    return out, (src.shape, coords_x, coords_y)


def _bsc_bwd(gather_dtype, residuals, g):
    src_shape, coords_x, coords_y = residuals
    # The op is linear in src, so its transpose (the scatter-add) can run on
    # the f32 core regardless of the forward's storage dtype; d/dsrc of the
    # bf16 cast is identity (same as autodiff's astype VJP).
    d_src, = jax.linear_transpose(
        lambda s: _lerp_gather(s, coords_x, coords_y),
        jax.ShapeDtypeStruct(src_shape, jnp.float32))(g.astype(jnp.float32))
    return d_src, jnp.zeros_like(coords_x), jnp.zeros_like(coords_y)


_bilinear_sample_cast.defvjp(_bsc_fwd, _bsc_bwd)


# every implementation homography_warp can run, the one place they are named
WARP_IMPLS = ("xla", "pallas", "pallas_diff")


def homography_coords(d_src: jnp.ndarray,
                      G_tgt_src: jnp.ndarray,
                      K_src_inv: jnp.ndarray,
                      K_tgt: jnp.ndarray,
                      meshgrid_tgt: jnp.ndarray,
                      src_hw: Tuple[int, int]):
    """The coordinate half of the warp: where each target pixel samples its
    source plane.

    For each batch element: compose H_tgt_src = K_tgt (R - t n^T / -d) K_src^-1,
    invert it (closed form, no grad — matching the reference's no_grad inverse,
    homography_sampler.py:112-113) and map the target pixel grid into source
    pixels. The coordinates are constants to autodiff (stop_gradient), so
    whatever is sampled or evaluated at them differentiates in its values
    alone. Reference: HomographySample.sample (homography_sampler.py:58-141).

    Args:
      d_src: [B'] plane depths; G_tgt_src: [B', 4, 4]
      K_src_inv, K_tgt: [B', 3, 3]
      meshgrid_tgt: [3, Ht, Wt] homogeneous target pixel grid
      src_hw: (H, W) of the source planes (decides `valid`)
    Returns:
      x, y [B', Ht, Wt] float32 source-pixel coordinates (unclipped: every
        sampler clips them to the pixel-centre box, grid_sample's border
        mode), valid [B', Ht, Wt] bool — the target pixel landed inside the
        source image.
    """
    H, W = src_hw
    Bp = d_src.shape[0]
    _, Ht, Wt = meshgrid_tgt.shape

    H_tgt_src = geometry.homography_tgt_src(K_tgt, K_src_inv, G_tgt_src, d_src)
    H_src_tgt = jax.lax.stop_gradient(geometry.inverse_3x3(H_tgt_src))

    grid = meshgrid_tgt.reshape(3, Ht * Wt)
    src_homo = jnp.einsum("bij,jn->bin", H_src_tgt, grid)  # [B',3,HtWt]
    src_xy = src_homo[:, 0:2, :] / src_homo[:, 2:3, :]
    x = src_xy[:, 0, :].reshape(Bp, Ht, Wt)
    y = src_xy[:, 1, :].reshape(Bp, Ht, Wt)

    valid = ((x > -1.0) & (x < float(W)) & (y > -1.0) & (y < float(H)))
    return x, y, valid


def sample_planes(src_BCHW: jnp.ndarray,
                  x: jnp.ndarray,
                  y: jnp.ndarray,
                  impl: str = "xla",
                  band: int = 16,
                  mesh=None,
                  mxu_dtype=jnp.float32,
                  with_subband_frac: bool = False):
    """The sampling half of the warp: bilinear-sample `src_BCHW` with border
    padding at `homography_coords`' (x, y), on the chosen implementation.

    Args:
      src_BCHW: [B', C, H, W] plane images (B' is typically B*S)
      x, y: [B', Ht, Wt] source-pixel coordinates
      impl: one of WARP_IMPLS. "xla" (gather; autodiffed; the reference and
        the guarded fallback), "pallas" (banded MXU gather kernel,
        forward-only; caller must validate the band via
        kernels.warp.band_span), or "pallas_diff" (banded fwd+bwd kernels
        with a built-in runtime gather fallback — the Pallas training
        backend). Anything else raises ValueError.
      mesh: ("data","plane") jax Mesh. With impl="pallas_diff" on a
        multi-device mesh the kernel runs under shard_map with the
        flat B' axis split over data*plane (matching the decoder's B*S
        layout, models/decoder.py shard_bs) — each device warps its local
        planes, no cross-device traffic.
      with_subband_frac: compute `subband_frac` (below) on pallas_diff;
        otherwise it is NaN (serving reads no metric, and every render
        program would pay the plan's trace a second time).
    Returns:
      tgt [B', C, Ht, Wt];
      in_domain, a scalar f32 diagnostic — the FRACTION of this call that
        took pallas_diff's fast path: 1.0 all-fast, 0.0 all on the runtime
        gather fallback, NaN for backends with no guard (plain xla /
        forward-only pallas). Under a sharded Pallas mesh the cond decides
        per shard, and the flag is the pmean of the per-shard guards over
        data*plane — e.g. 0.75 when one of four shards drew an out-of-band
        pose. Powers the `warp_fallback_frac` training metric;
      subband_frac, a scalar f32 — the share of this call's (output row,
        lane tile) units that the pallas_diff kernels contracted against
        their window alone (kernels/warp.subband_frac), 0.0 for the part of
        the call on the gather fallback, NaN for the other backends.
        Sharded like `in_domain`. Powers `warp_subband_frac`.
    """
    if impl not in WARP_IMPLS:
        raise ValueError(
            f"sample_planes impl={impl!r}: must be one of {WARP_IMPLS}")
    Bp = src_BCHW.shape[0]

    # diagnostics only — mirror pallas_diff's fallback decision
    # (NaN = backend has no runtime guard to measure)
    in_domain = jnp.full((), jnp.nan, jnp.float32)
    subband = jnp.full((), jnp.nan, jnp.float32)

    if impl == "pallas":
        from mine_tpu.kernels import on_tpu_backend
        from mine_tpu.kernels.warp import pallas_bilinear_sample
        tgt = pallas_bilinear_sample(src_BCHW, x, y, band=band,
                                     interpret=not on_tpu_backend())
    elif impl == "pallas_diff":
        # training path: Pallas fwd+bwd with runtime gather fallback
        # outside the band's domain (kernels/warp_vjp.py). Coords are
        # non-learnable (no-grad inverse in homography_coords), so
        # stop_gradient keeps the two branches' autodiff structurally
        # identical.
        from mine_tpu.kernels import on_tpu_backend
        from mine_tpu.kernels.warp_vjp import (
            bilinear_sample_diff_guarded, guard_ok, guarded_subband_frac)

        def _subband(src_shape, cx, cy):
            if not with_subband_frac:
                return subband  # NaN: nobody asked
            return guarded_subband_frac(src_shape, cx, cy, band)
        fn = functools.partial(bilinear_sample_diff_guarded,
                               band=band,
                               interpret=not on_tpu_backend(),
                               mxu_dtype=mxu_dtype)
        xs = jax.lax.stop_gradient(x)
        ys = jax.lax.stop_gradient(y)
        if mesh is not None and mesh.size > 1:
            if Bp % mesh.size == 0:
                # split the flat B' (=B*S, B-major) axis over data*plane:
                # lines up with the decoder's shard_bs layout, so the volume
                # is already local — the per-device kernel sees only its
                # planes (and the band-domain cond decides per shard)
                from jax.sharding import PartitionSpec as P

                from mine_tpu.parallel.mesh import (DATA_AXIS, PLANE_AXIS,
                                                    shard_map)
                bs_axes = (DATA_AXIS, PLANE_AXIS)

                def sharded(s, cx, cy):
                    # the guard runs on the LOCAL shard's coords — exactly
                    # the cond each device's kernel takes — and pmean over
                    # both mesh axes yields the FRACTION of shards on the
                    # fast path (the windows' share likewise: each shard's
                    # own)
                    def over_shards(v):
                        return jax.lax.pmean(jax.lax.pmean(v, DATA_AXIS),
                                             PLANE_AXIS)
                    ok = guard_ok(s.shape, cy, band).astype(jnp.float32)
                    return (fn(s, cx, cy), over_shards(ok),
                            over_shards(_subband(s.shape, cx, cy)))

                sharded = shard_map(
                    sharded, mesh=mesh,
                    in_specs=(P(bs_axes), P(bs_axes), P(bs_axes)),
                    out_specs=(P(bs_axes), P(), P()))
                return sharded(src_BCHW, xs, ys)
            # a bare pallas_call inside a GSPMD-partitioned program has
            # no partitioning spec — fall back to the autodiffed gather
            # for non-divisible batches (e.g. remainder eval examples);
            # keep the reduced-precision storage knob on this path too
            fn = functools.partial(bilinear_sample,
                                   gather_dtype=mxu_dtype)
            in_domain = jnp.zeros((), jnp.float32)
            if with_subband_frac:
                subband = jnp.zeros((), jnp.float32)
        else:
            in_domain = guard_ok(src_BCHW.shape, ys,
                                 band).astype(jnp.float32)
            subband = _subband(src_BCHW.shape, xs, ys)
        tgt = fn(src_BCHW, xs, ys)
    else:  # "xla"
        # training.warp_dtype reaches the gather too: bf16 storage halves
        # the volume's HBM traffic, lerp stays f32 (f32 is a no-op knob)
        tgt = bilinear_sample(src_BCHW, x, y, gather_dtype=mxu_dtype)
    return tgt, in_domain, subband


def homography_warp(src_BCHW: jnp.ndarray,
                    d_src: jnp.ndarray,
                    G_tgt_src: jnp.ndarray,
                    K_src_inv: jnp.ndarray,
                    K_tgt: jnp.ndarray,
                    meshgrid_tgt: jnp.ndarray,
                    impl: str = "xla",
                    band: int = 16,
                    mesh=None,
                    mxu_dtype=jnp.float32,
                    with_domain_flag: bool = False,
                    with_subband_frac: bool = False):
    """Warp source-plane images into the target camera via inverse homography:
    `homography_coords` then `sample_planes` (their docstrings hold the
    arguments; ops/rendering.render_tgt_rgb_depth calls the two halves itself,
    because the composite evaluates the plane points at the same coordinates).

    Returns:
      tgt [B', C, Ht, Wt], valid_mask [B', Ht, Wt] (bool)
      [, in_domain scalar f32 — only when with_domain_flag]
      [, subband_frac scalar f32 — only when with_subband_frac]
    """
    x, y, valid = homography_coords(d_src, G_tgt_src, K_src_inv, K_tgt,
                                    meshgrid_tgt, src_BCHW.shape[-2:])
    tgt, in_domain, subband = sample_planes(
        src_BCHW, x, y, impl=impl, band=band, mesh=mesh, mxu_dtype=mxu_dtype,
        with_subband_frac=with_subband_frac)
    return (tgt, valid) + ((in_domain,) if with_domain_flag else ()) \
        + ((subband,) if with_subband_frac else ())
