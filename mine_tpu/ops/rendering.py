"""Differentiable MPI volume rendering.

Replaces the reference's operations/mpi_rendering.py with pure jnp functions.
Array convention: plane volumes are [B, S, C, H, W] (S = number of MPI planes,
nearest first), matching the reference's documented shapes; W is the
minor-most axis so elementwise work vectorizes over full TPU lanes.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from mine_tpu import geometry
from mine_tpu.ops import warp
from mine_tpu.parallel.mesh import DATA_AXIS, PLANE_AXIS, constrain, shard_map


def alpha_composition(alpha_BK1HW: jnp.ndarray,
                      value_BKCHW: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Classic MPI over-compositing: w_k = a_k * prod_{j<k}(1 - a_j).

    k=0 is the nearest plane. Reference: mpi_rendering.alpha_composition
    (mpi_rendering.py:23-39).

    Returns: (composed [B,C,H,W], weights [B,K,1,H,W])
    """
    preserve = jnp.cumprod(1.0 - alpha_BK1HW, axis=1)
    preserve = jnp.concatenate(
        [jnp.ones_like(preserve[:, :1]), preserve[:, :-1]], axis=1)
    weights = alpha_BK1HW * preserve
    composed = jnp.sum(value_BKCHW * weights, axis=1)
    return composed, weights


def finalize_depth(depth_acc: jnp.ndarray,
                   weights_sum: jnp.ndarray,
                   is_bg_depth_inf: bool) -> jnp.ndarray:
    """Depth finalization shared by every composite backend: weight-normalize,
    or add a far background (+1000*(1-w_sum)) when `is_bg_depth_inf` (DTU
    mode). Reference: mpi_rendering.weighted_sum_mpi (mpi_rendering.py:74-77).
    """
    if is_bg_depth_inf:
        return depth_acc + (1.0 - weights_sum) * 1000.0
    return depth_acc / (weights_sum + 1e-5)


def weighted_sum_mpi(rgb_BS3HW: jnp.ndarray,
                     xyz_BS3HW: jnp.ndarray,
                     weights: jnp.ndarray,
                     is_bg_depth_inf: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Composite rgb and depth from per-plane weights.

    Reference: mpi_rendering.weighted_sum_mpi (mpi_rendering.py:70-82).
    """
    weights_sum = jnp.sum(weights, axis=1)  # [B,1,H,W]
    rgb_out = jnp.sum(weights * rgb_BS3HW, axis=1)  # [B,3,H,W]
    depth_acc = jnp.sum(weights * xyz_BS3HW[:, :, 2:3], axis=1)
    return rgb_out, finalize_depth(depth_acc, weights_sum, is_bg_depth_inf)


def plane_volume_rendering(rgb_BS3HW: jnp.ndarray,
                           sigma_BS1HW: jnp.ndarray,
                           xyz_BS3HW: jnp.ndarray,
                           is_bg_depth_inf: bool):
    """Volume rendering over MPI planes with density sigma.

    transparency_s = exp(-sigma_s * dist_s) where dist_s is the distance
    between consecutive plane points along the ray (last plane: 1e3);
    accumulated transparency is the exclusive cumulative product (with the
    reference's +1e-6 stabilizer, mpi_rendering.py:59); weights = T_acc*alpha.
    Reference: mpi_rendering.plane_volume_rendering (mpi_rendering.py:42-67).

    Returns: (rgb [B,3,H,W], depth [B,1,H,W],
              transparency_acc [B,S,1,H,W], weights [B,S,1,H,W])
    """
    xyz_diff = xyz_BS3HW[:, 1:] - xyz_BS3HW[:, :-1]  # [B,S-1,3,H,W]
    dist = jnp.linalg.norm(xyz_diff, axis=2, keepdims=True)  # [B,S-1,1,H,W]
    dist = jnp.concatenate(
        [dist, jnp.full_like(dist[:, :1], 1e3)], axis=1)  # [B,S,1,H,W]

    transparency = jnp.exp(-sigma_BS1HW * dist)
    alpha = 1.0 - transparency

    transparency_acc = jnp.cumprod(transparency + 1e-6, axis=1)
    transparency_acc = jnp.concatenate(
        [jnp.ones_like(transparency_acc[:, :1]), transparency_acc[:, :-1]], axis=1)

    weights = transparency_acc * alpha
    rgb_out, depth_out = weighted_sum_mpi(rgb_BS3HW, xyz_BS3HW, weights,
                                          is_bg_depth_inf)
    return rgb_out, depth_out, transparency_acc, weights


def render(rgb_BS3HW: jnp.ndarray,
           sigma_BS1HW: jnp.ndarray,
           xyz_BS3HW: jnp.ndarray,
           use_alpha: bool = False,
           is_bg_depth_inf: bool = False):
    """Dispatch sigma-density vs alpha compositing modes.

    Reference: mpi_rendering.render (mpi_rendering.py:7-20).

    Returns: (rgb [B,3,H,W], depth [B,1,H,W], blend_weights, weights
              [B,S,1,H,W]). blend_weights is transparency_acc [B,S,1,H,W] in
              sigma mode but zeros_like(rgb) [B,S,3,H,W] in alpha mode — the
              mode-dependent shape mirrors the reference (mpi_rendering.py:19).
    """
    if not use_alpha:
        return plane_volume_rendering(rgb_BS3HW, sigma_BS1HW, xyz_BS3HW,
                                      is_bg_depth_inf)
    imgs_syn, weights = alpha_composition(sigma_BS1HW, rgb_BS3HW)
    depth_syn, _ = alpha_composition(sigma_BS1HW, xyz_BS3HW[:, :, 2:3])
    blend_weights = jnp.zeros_like(rgb_BS3HW)
    return imgs_syn, depth_syn, blend_weights, weights


_warned_fallbacks = set()


def _warn_backend_fallback(backend: str, why: str) -> None:
    """One-time trace-time notice when a configured composite backend is
    silently overridden (runs during tracing, so it fires once per compile,
    not per step)."""
    key = (backend, why)
    if key not in _warned_fallbacks:
        _warned_fallbacks.add(key)
        import warnings
        warnings.warn(
            f"composite backend {backend!r} falling back to 'xla': {why}")


class TgtRender(NamedTuple):
    rgb: jnp.ndarray    # [B,3,H,W]
    depth: jnp.ndarray  # [B,1,H,W]
    mask: jnp.ndarray   # [B,1,H,W] — number of planes whose warp was in-bounds
    # scalar f32 guard diagnostic: 1.0 = guarded warp backend took its fast
    # path this call, 0.0 = runtime gather fallback, NaN = backend has no
    # guard (ops/warp.homography_warp with_domain_flag)
    warp_in_domain: jnp.ndarray = None
    # scalar f32: share of the banded Pallas warp's (row, lane tile) units
    # contracted against their window alone; NaN on the other backends
    # (ops/warp.homography_warp with_subband_frac)
    warp_subband: jnp.ndarray = None


def render_tgt_rgb_depth(mpi_rgb_src: jnp.ndarray,
                         mpi_sigma_src: jnp.ndarray,
                         mpi_disparity_src: jnp.ndarray,
                         G_tgt_src: jnp.ndarray,
                         K_src_inv: jnp.ndarray,
                         K_tgt: jnp.ndarray,
                         use_alpha: bool = False,
                         is_bg_depth_inf: bool = False,
                         backend: str = "xla",
                         warp_impl: str = "xla",
                         warp_band: int = 16,
                         warp_dtype: str = "float32",
                         mesh=None) -> TgtRender:
    """Render the MPI into a target camera.

    Concatenates [rgb, sigma] into a 4-channel plane volume, warps all S
    planes with per-plane homographies (flattened to a B*S batch), evaluates
    the target-frame plane points at the warp's own (border-clipped) source
    coordinates, zeroes density where that point is behind the target camera
    (z<0), and composites. The reference (mpi_rendering.render_tgt_rgb_depth,
    mpi_rendering.py:181-241) warps the points as three more channels; a
    plane's points are affine in the source pixel, so their bilinear sample
    is geometry.plane_xyz_tgt_at's formula, in float32 and with no gradient
    for the backward warp to carry.

    Args:
      mpi_rgb_src: [B,S,3,H,W]; mpi_sigma_src: [B,S,1,H,W]
      mpi_disparity_src: [B,S]
      G_tgt_src: [B,4,4]; K_src_inv, K_tgt: [B,3,3]
      mesh: ("data","plane") Mesh — on multi-device meshes the Pallas
        backends run under shard_map (warp: B*S split over data*plane;
        composite: batch over "data" with the plane axis gathered locally,
        since the transparency chain reduces over S).
    """
    B, S, _, H, W = mpi_rgb_src.shape
    mpi_depth_src = 1.0 / mpi_disparity_src  # [B,S]

    volume = jnp.concatenate([mpi_rgb_src, mpi_sigma_src], axis=2)
    volume_bs = volume.reshape(B * S, 4, H, W)

    def expand(x):
        return jnp.repeat(x, S, axis=0)  # [B,...] -> [B*S,...] (plane-major per b)

    d_bs = mpi_depth_src.reshape(B * S)
    G_bs, K_src_inv_bs = expand(G_tgt_src), expand(K_src_inv)
    src_x, src_y, valid = warp.homography_coords(
        d_bs, G_bs, K_src_inv_bs, expand(K_tgt),
        geometry.cached_pixel_grid(H, W), (H, W))
    warped, warp_in_domain, warp_subband = warp.sample_planes(
        volume_bs, src_x, src_y,
        impl=warp_impl,
        band=warp_band,
        mesh=mesh,
        mxu_dtype=jnp.bfloat16 if warp_dtype == "bfloat16" else jnp.float32,
        with_subband_frac=True,
    )

    warped = warped.reshape(B, S, 4, H, W)
    tgt_rgb = warped[:, :, 0:3]
    tgt_sigma = warped[:, :, 3:4]
    # the clip is grid_sample(border)'s, the one every sampler applies
    # (ops/warp._lerp_gather, kernels/warp.band_plan): out-of-image pixels
    # read the point of the border texel they sample
    tgt_xyz = geometry.plane_xyz_tgt_at(
        jnp.clip(src_x, 0.0, W - 1.0), jnp.clip(src_y, 0.0, H - 1.0),
        d_bs, G_bs, K_src_inv_bs).reshape(B, S, 3, H, W)
    # on a train mesh the points lie as train/loss.py lays out the MPI they
    # are composited with. (The serve fleet passes no mesh: its program is
    # partitioned from its operands' shardings alone, and
    # tests/test_serve_fleet.py holds its result to a single device's.)
    tgt_xyz = constrain(tgt_xyz, mesh, DATA_AXIS, PLANE_AXIS)

    if mesh is not None and mesh.size > 1 \
            and B % mesh.shape.get("data", 1) != 0 and backend != "xla":
        # non-divisible batch (e.g. a remainder eval example): a bare
        # pallas_call inside a GSPMD program carries no partitioning spec,
        # so use the XLA composite instead of shard_map
        _warn_backend_fallback(backend, "batch not divisible by data axis")
        backend = "xla"

    if backend == "plane_scan":
        # distributed two-level transparency scan over the plane axis
        # (ops/plane_scan.py) — the volume stays plane-sharded end to end.
        # Requires a multi-device plane-divisible mesh (see the config
        # comment in params_default.yaml); otherwise the XLA composite.
        if not (mesh is not None and mesh.size > 1 and not use_alpha
                and S % mesh.shape.get(PLANE_AXIS, 1) == 0):
            _warn_backend_fallback(
                backend, "needs a multi-device mesh with S divisible by the "
                "plane axis (and sigma mode)")
            backend = "xla"

    if backend in ("pallas", "pallas_diff") and use_alpha:
        # the fused kernels implement the sigma-density composite only
        _warn_backend_fallback(backend, "mpi.use_alpha uses the XLA "
                               "alpha-compositing path")
        backend = "xla"

    # Arbitrary heights are fine on the Pallas backends: the kernel
    # wrappers pad rows to a Mosaic-legal multiple of 8 internally
    # (kernels/composite.py pad_rows) and slice the outputs.

    if backend == "plane_scan":
        from mine_tpu.ops.plane_scan import plane_sharded_volume_render
        rgb_syn, depth_syn = plane_sharded_volume_render(
            tgt_rgb, tgt_sigma, tgt_xyz, mesh,
            z_mask=True, is_bg_depth_inf=is_bg_depth_inf)
    elif backend in ("pallas", "pallas_diff"):
        # fused composite: z-masking + volume rendering in one HBM pass
        # (mine_tpu.kernels.composite). "pallas" is forward-only;
        # "pallas_diff" adds the custom-VJP backward kernel for training.
        from mine_tpu.kernels import on_tpu_backend
        interp = not on_tpu_backend()
        if backend == "pallas_diff":
            from mine_tpu.kernels.composite_vjp import fused_volume_render_diff
            fn = lambda r, s, x: fused_volume_render_diff(  # noqa: E731
                r, s, x, True, is_bg_depth_inf, interp)
        else:
            from mine_tpu.kernels.composite import fused_volume_render
            fn = lambda r, s, x: fused_volume_render(  # noqa: E731
                r, s, x, z_mask=True,
                is_bg_depth_inf=is_bg_depth_inf, interpret=interp)
        if mesh is not None and mesh.size > 1:
            # batch over "data"; the plane axis is gathered to each device
            # (the transparency cumprod chains over S — a distributed scan
            # over "plane" is possible but the all-gather of the warped volume
            # matches what GSPMD inserts for the XLA composite anyway)
            from jax.sharding import PartitionSpec as P
            fn = shard_map(fn, mesh=mesh,
                           in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
                           out_specs=(P(DATA_AXIS), P(DATA_AXIS)))
        rgb_syn, depth_syn = fn(tgt_rgb, tgt_sigma, tgt_xyz)
    else:
        tgt_z = tgt_xyz[:, :, 2:3]
        tgt_sigma = jnp.where(tgt_z >= 0.0, tgt_sigma, 0.0)
        rgb_syn, depth_syn, _, _ = render(tgt_rgb, tgt_sigma, tgt_xyz,
                                          use_alpha=use_alpha,
                                          is_bg_depth_inf=is_bg_depth_inf)
    mask = jnp.sum(valid.reshape(B, S, H, W).astype(jnp.float32),
                   axis=1, keepdims=True)  # [B,1,H,W]
    return TgtRender(rgb=rgb_syn, depth=depth_syn, mask=mask,
                     warp_in_domain=warp_in_domain,
                     warp_subband=warp_subband)


def predict_mpi_coarse_to_fine(mpi_predictor,
                               key: jax.Array,
                               src_imgs: jnp.ndarray,
                               xyz_src_BS3HW_coarse: jnp.ndarray,
                               disparity_coarse_src: jnp.ndarray,
                               s_fine: int,
                               is_bg_depth_inf: bool,
                               fine_rows=None):
    """Optional coarse-to-fine plane placement.

    With s_fine > 0: run a stop-gradient coarse pass, convert per-plane mean
    compositing weights into a pdf over disparity, importance-sample s_fine
    extra disparities (inverse CDF), merge + sort descending, and run the full
    pass on the S_coarse+s_fine planes. Both passes have static shapes.
    Reference: mpi_rendering.predict_mpi_coarse_to_fine
    (mpi_rendering.py:244-271).

    Args:
      mpi_predictor: fn (src_imgs, disparity [B,S]) -> list of 4 per-scale
        MPI volumes [B,S,4,Hs,Ws]
      fine_rows: optional (full_batch, row) for a per-example caller
        standing in for rows [row:row+B] of a `full_batch`-sized batched
        call: the fine-plane uniforms are drawn with `key` at the FULL
        batch shape and this caller's rows sliced out, so the importance
        samples match the batched pass's for the same example (the
        encode-once eval path, train/step.py eval_encode_c2f).
    Returns: (mpi_all_src_list, disparity_all_src [B, S_coarse+s_fine])
    """
    from mine_tpu.ops import sampling  # local import to avoid cycle

    if s_fine <= 0:
        return mpi_predictor(src_imgs, disparity_coarse_src), disparity_coarse_src

    B, S_coarse = disparity_coarse_src.shape

    coarse_list = mpi_predictor(src_imgs, disparity_coarse_src)
    coarse = jax.lax.stop_gradient(coarse_list[0])
    rgb_c = coarse[:, :, 0:3]
    sigma_c = coarse[:, :, 3:4]
    _, _, _, weights = plane_volume_rendering(
        rgb_c, sigma_c, jax.lax.stop_gradient(xyz_src_BS3HW_coarse),
        is_bg_depth_inf)
    weights = jnp.mean(weights, axis=(2, 3, 4))[:, None, None, :]  # [B,1,1,S]

    if fine_rows is None:
        disp_fine = sampling.sample_pdf(
            key, disparity_coarse_src[:, None, None, :], weights, s_fine)
    else:
        full_batch, row = fine_rows
        u = jax.random.uniform(key, (full_batch, 1, 1, s_fine),
                               dtype=weights.dtype)
        u = jax.lax.dynamic_slice_in_dim(u, row, B, axis=0)
        disp_fine = sampling.sample_pdf_from_u(
            u, disparity_coarse_src[:, None, None, :], weights)
    disp_fine = disp_fine[:, 0, 0, :]  # [B, s_fine]

    disparity_all = jnp.concatenate([disparity_coarse_src, disp_fine], axis=1)
    disparity_all = -jnp.sort(-disparity_all, axis=1)  # descending
    disparity_all = jax.lax.stop_gradient(disparity_all)

    return mpi_predictor(src_imgs, disparity_all), disparity_all
