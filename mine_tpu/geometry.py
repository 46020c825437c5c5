"""Pure-functional camera/plane geometry.

Replaces the math of the reference's operations/homography_sampler.py (plane
homographies, pixel meshgrids) and operations/rendering_utils.py
(transform_G_xyz), plus utils.py:96-117 (its CUDA `torch.inverse` retry hack —
unnecessary under XLA: we use closed-form adjugate/rigid inverses which are
exact and fuse cleanly).

Conventions (same as reference):
  * pixel coordinates: x right, y down; homogeneous pixel = [x, y, 1]
  * K maps camera coords to pixels; G_a_b maps points in frame b to frame a
  * MPI planes are fronto-parallel in the source frame, plane s at depth
    d_s = 1 / disparity_s, plane equation n^T X - d = 0 with n = [0, 0, 1]

All functions are shape-polymorphic over leading batch dims where noted and
safe to call under jit; meshgrids become compile-time constants.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np


def pixel_grid_homogeneous(height: int, width: int) -> np.ndarray:
    """Homogeneous pixel-center grid, shape [3, H, W] rows (x, y, 1).

    Matches reference HomographySample.grid_generation
    (homography_sampler.py:24-33): x in [0, W-1], y in [0, H-1].

    Returned as numpy (not jnp) on purpose: callers may run under different
    jit traces, and a host-cached numpy constant embeds safely in each —
    whereas a cached jnp array created inside one trace would leak its tracer
    into the next.
    """
    x = np.arange(width, dtype=np.float32)
    y = np.arange(height, dtype=np.float32)
    xv, yv = np.meshgrid(x, y)  # HxW each
    return np.stack([xv, yv, np.ones_like(xv)], axis=0)  # 3xHxW


def inverse_3x3(mat: jnp.ndarray) -> jnp.ndarray:
    """Closed-form adjugate inverse of [..., 3, 3] matrices."""
    a, b, c = mat[..., 0, 0], mat[..., 0, 1], mat[..., 0, 2]
    d, e, f = mat[..., 1, 0], mat[..., 1, 1], mat[..., 1, 2]
    g, h, i = mat[..., 2, 0], mat[..., 2, 1], mat[..., 2, 2]

    co_a = e * i - f * h
    co_b = -(d * i - f * g)
    co_c = d * h - e * g
    det = a * co_a + b * co_b + c * co_c

    adj = jnp.stack([
        jnp.stack([co_a, -(b * i - c * h), b * f - c * e], axis=-1),
        jnp.stack([co_b, a * i - c * g, -(a * f - c * d)], axis=-1),
        jnp.stack([co_c, -(a * h - b * g), a * e - b * d], axis=-1),
    ], axis=-2)
    return adj / det[..., None, None]


def inverse_intrinsics(K: jnp.ndarray) -> jnp.ndarray:
    """Exact inverse of [..., 3, 3] intrinsics [[fx,0,cx],[0,fy,cy],[0,0,1]]."""
    fx, fy = K[..., 0, 0], K[..., 1, 1]
    cx, cy = K[..., 0, 2], K[..., 1, 2]
    zero = jnp.zeros_like(fx)
    one = jnp.ones_like(fx)
    rows = [
        jnp.stack([1.0 / fx, zero, -cx / fx], axis=-1),
        jnp.stack([zero, 1.0 / fy, -cy / fy], axis=-1),
        jnp.stack([zero, zero, one], axis=-1),
    ]
    return jnp.stack(rows, axis=-2)


def rigid_inverse(G: jnp.ndarray) -> jnp.ndarray:
    """Inverse of [..., 4, 4] rigid transforms: [R|t] -> [R^T | -R^T t].

    The reference inverts G_src_tgt with a retrying `torch.inverse`
    (synthesis_task.py:208, utils.py:96-117); G is always a relative camera
    pose (product of rigid world-to-camera transforms, nerf_dataset.py:216),
    so the closed form is exact.
    """
    R = G[..., :3, :3]
    t = G[..., :3, 3]
    Rt = jnp.swapaxes(R, -1, -2)
    t_inv = -jnp.einsum("...ij,...j->...i", Rt, t)
    top = jnp.concatenate([Rt, t_inv[..., :, None]], axis=-1)  # [...,3,4]
    bottom = jnp.broadcast_to(
        jnp.asarray([0.0, 0.0, 0.0, 1.0], dtype=G.dtype), G.shape[:-2] + (1, 4))
    return jnp.concatenate([top, bottom], axis=-2)


def scale_intrinsics(K: jnp.ndarray, scale: int) -> jnp.ndarray:
    """Intrinsics for a 2**scale-downsampled image: K/2**s with K[2,2]=1.

    Reference: synthesis_task.py:238-241.
    """
    K_scaled = K / (2.0 ** scale)
    return K_scaled.at[..., 2, 2].set(1.0)


def transform_points(G: jnp.ndarray, xyz: jnp.ndarray) -> jnp.ndarray:
    """Apply [..., 4, 4] homogeneous transforms to [..., 3, N] points.

    Reference: rendering_utils.transform_G_xyz (rendering_utils.py:5-24).
    """
    R = G[..., :3, :3]
    t = G[..., :3, 3]
    return jnp.einsum("...ij,...jn->...in", R, xyz) + t[..., :, None]


def homography_tgt_src(K_tgt: jnp.ndarray,
                       K_src_inv: jnp.ndarray,
                       G_tgt_src: jnp.ndarray,
                       d_src: jnp.ndarray) -> jnp.ndarray:
    """Plane-induced homography mapping src pixels to tgt pixels.

    H_tgt_src = K_tgt (R - t n^T / -d) K_src^-1 for the fronto-parallel source
    plane n=[0,0,1], n^T X - d = 0 (reference: homography_sampler.py:101-108).

    Args:
      K_tgt, K_src_inv: [..., 3, 3]
      G_tgt_src: [..., 4, 4]
      d_src: [...] plane depth in the source frame
    Returns: [..., 3, 3]
    """
    R = G_tgt_src[..., :3, :3]
    t = G_tgt_src[..., :3, 3]
    n = jnp.asarray([0.0, 0.0, 1.0], dtype=K_tgt.dtype)
    t_nT = t[..., :, None] * n[None, :]  # [..., 3, 3]
    R_tnd = R - t_nT / (-d_src)[..., None, None]
    return K_tgt @ R_tnd @ K_src_inv


def plane_xyz_src(meshgrid_homo: jnp.ndarray,
                  mpi_disparity_src: jnp.ndarray,
                  K_src_inv: jnp.ndarray) -> jnp.ndarray:
    """Per-plane 3D points of the MPI in the source frame.

    xyz(s, p) = K^-1 * pixel_p / disparity_s for every plane s and pixel p.
    Reference: mpi_rendering.get_src_xyz_from_plane_disparity
    (mpi_rendering.py:140-163).

    Args:
      meshgrid_homo: [3, H, W]
      mpi_disparity_src: [B, S]
      K_src_inv: [B, 3, 3]
    Returns: xyz_src [B, S, 3, H, W]
    """
    _, H, W = meshgrid_homo.shape
    depth = 1.0 / mpi_disparity_src  # [B, S]
    # K^-1 * grid: [B, 3, HW] (independent of s)
    rays = jnp.einsum("bij,jn->bin", K_src_inv, meshgrid_homo.reshape(3, H * W))
    xyz = rays[:, None, :, :] * depth[:, :, None, None]  # [B, S, 3, HW]
    return xyz.reshape(depth.shape[0], depth.shape[1], 3, H, W)


def plane_xyz_tgt(xyz_src_BS3HW: jnp.ndarray, G_tgt_src: jnp.ndarray) -> jnp.ndarray:
    """Rigid-transform per-plane source points into the target frame.

    Reference: mpi_rendering.get_tgt_xyz_from_plane_disparity
    (mpi_rendering.py:166-178).

    Args:
      xyz_src_BS3HW: [B, S, 3, H, W]
      G_tgt_src: [B, 4, 4]
    Returns: [B, S, 3, H, W]
    """
    B, S, _, H, W = xyz_src_BS3HW.shape
    R = G_tgt_src[:, :3, :3]
    t = G_tgt_src[:, :3, 3]
    xyz = jnp.einsum("bij,bsjn->bsin", R, xyz_src_BS3HW.reshape(B, S, 3, H * W))
    xyz = xyz + t[:, None, :, None]
    return xyz.reshape(B, S, 3, H, W)


def plane_xyz_tgt_at(x: jnp.ndarray,
                     y: jnp.ndarray,
                     d_src: jnp.ndarray,
                     G_tgt_src: jnp.ndarray,
                     K_src_inv: jnp.ndarray) -> jnp.ndarray:
    """Target-frame plane points at continuous source pixels, in closed form.

    plane_xyz_tgt(plane_xyz_src(...)) is, for one plane, affine in the source
    pixel: X(x, y) = d (R K_src^-1) [x, y, 1] + t. Bilinear interpolation
    reproduces an affine field exactly, so this IS the field sampled at
    (x, y) — without the field, the gather or the MXU. Written as broadcast
    multiply-adds in float32 (a default-precision einsum over the pixels
    would be one bfloat16 pass on the TPU); plain autodiff, so it
    differentiates in the depths and in G where the sampled field did.

    One row a plane, as ops/warp.homography_coords takes them (B' is
    typically B*S).

    Args:
      x, y: [B', H, W] source-pixel coordinates (the caller clips them to
        the image where it wants border semantics)
      d_src: [B'] plane depths; G_tgt_src: [B', 4, 4]; K_src_inv: [B', 3, 3]
    Returns: [B', 3, H, W]
    """
    A = jnp.einsum("bij,bjk->bik", G_tgt_src[:, :3, :3], K_src_inv,
                   precision="highest")[:, :, :, None, None]  # [B',3,3,1,1]
    t = G_tgt_src[:, :3, 3, None, None]  # [B',3,1,1]
    x, y = x[:, None], y[:, None]  # [B',1,H,W]
    return d_src[:, None, None, None] \
        * (A[:, :, 0] * x + A[:, :, 1] * y + A[:, :, 2]) + t


def intrinsics_from_fov(height: int, width: int, fov_degrees: float = 90.0) -> np.ndarray:
    """Pinhole K from a horizontal FoV (reference: image_to_video.py:192-202)."""
    fov = np.deg2rad(fov_degrees)
    fx = width * 0.5 / np.tan(fov * 0.5)
    return np.array([[fx, 0.0, width * 0.5],
                     [0.0, fx, height * 0.5],
                     [0.0, 0.0, 1.0]], dtype=np.float32)


@functools.lru_cache(maxsize=None)
def cached_pixel_grid(height: int, width: int) -> np.ndarray:
    """Host-cached numpy meshgrid; becomes an XLA constant in each jit trace."""
    return pixel_grid_homogeneous(height, width)
