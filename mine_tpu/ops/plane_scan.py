"""Plane-sharded MPI volume rendering — a distributed transparency scan.

This is the workload's true "sequence parallelism" (SURVEY.md section 5,
long-context row): the reference keeps the whole S-plane volume on one
device and composites with a serial cumprod (mpi_rendering.py:42-67); the
GSPMD fallback for an S-sharded volume is an all-gather of the full
warped volume. Here each device composites ONLY its local planes and the
cross-shard combination rides two tiny collectives:

  1. one `ppermute` halo exchange of the FIRST plane's xyz per shard (the
     plane-distance term needs the next plane, so shard boundaries need one
     neighbor slice — [B,3,H,W] instead of the whole volume);
  2. one `all_gather` of each shard's TOTAL transparency product
     ([B,1,H,W] per shard) from which every shard forms the exclusive
     prefix product entering its block — the classic two-level scan
     (local scan + combine on block aggregates);
  3. one `psum` of the per-shard weighted rgb/depth/weight partials.

Per-device HBM traffic scales with S/P planes plus three plane-count-
independent exchanges, vs. the all-gather's full S. All math matches
ops/rendering.plane_volume_rendering bit-for-bit semantics, including the
reference's +1e-6 cumprod stabilizer (mpi_rendering.py:59) and the 1e3
far-plane distance, and everything is plain differentiable jnp + JAX
collectives, so jax.grad flows through the shard_map.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mine_tpu.parallel.mesh import DATA_AXIS, PLANE_AXIS


def _local_composite(rgb, sigma, xyz, z_mask: bool, axis: str):
    """Per-shard body: local chain + cross-shard combine. Shapes are the
    LOCAL shard's [B, S_loc, C, H, W]."""
    B, S_loc, _, H, W = rgb.shape
    idx = jax.lax.axis_index(axis)
    n_shards = jax.lax.axis_size(axis)

    if z_mask:
        sigma = jnp.where(xyz[:, :, 2:3] >= 0.0, sigma, 0.0)

    # ---- halo: first xyz plane of the NEXT shard (left-shift permute) ----
    first_xyz = xyz[:, :1]  # [B,1,3,H,W]
    perm = [(i, (i - 1) % n_shards) for i in range(n_shards)]
    next_first_xyz = jax.lax.ppermute(first_xyz, axis, perm)

    # plane distances: within-shard diffs + boundary diff to the halo slice;
    # the GLOBAL last plane gets the reference's 1e3 far distance
    xyz_ext = jnp.concatenate([xyz, next_first_xyz], axis=1)
    dist = jnp.linalg.norm(xyz_ext[:, 1:] - xyz_ext[:, :-1],
                           axis=2, keepdims=True)  # [B,S_loc,1,H,W]
    is_last_shard = idx == n_shards - 1
    last_dist = jnp.where(is_last_shard, 1e3, dist[:, -1])
    dist = dist.at[:, -1].set(last_dist)

    transparency = jnp.exp(-sigma * dist)
    alpha = 1.0 - transparency
    stabilized = transparency + 1e-6

    # local exclusive cumulative product + the shard's total product
    cum = jnp.cumprod(stabilized, axis=1)
    excl = jnp.concatenate([jnp.ones_like(cum[:, :1]), cum[:, :-1]], axis=1)
    total = cum[:, -1]  # [B,1,H,W]

    # ---- combine: exclusive prefix over shard totals ----
    totals = jax.lax.all_gather(total, axis)          # [P,B,1,H,W]
    shard_ids = jax.lax.broadcasted_iota(jnp.int32, (n_shards, 1, 1, 1, 1), 0)
    masked = jnp.where(shard_ids < idx, totals, jnp.ones_like(totals))
    prefix = jnp.prod(masked, axis=0)                 # [B,1,H,W]

    weights = prefix[:, None] * excl * alpha          # [B,S_loc,1,H,W]
    rgb_part = jnp.sum(weights * rgb, axis=1)         # [B,3,H,W]
    depth_part = jnp.sum(weights * xyz[:, :, 2:3], axis=1)
    wsum_part = jnp.sum(weights, axis=1)

    out = jax.lax.psum(
        jnp.concatenate([rgb_part, depth_part, wsum_part], axis=1), axis)
    return out  # [B,5,H,W] replicated over the plane axis


@functools.partial(jax.jit, static_argnames=("z_mask", "is_bg_depth_inf",
                                             "mesh"))
def plane_sharded_volume_render(rgb_BS3HW: jnp.ndarray,
                                sigma_BS1HW: jnp.ndarray,
                                xyz_BS3HW: jnp.ndarray,
                                mesh,
                                z_mask: bool = False,
                                is_bg_depth_inf: bool = False
                                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Distributed equivalent of rendering.plane_volume_rendering (+ z-mask).

    The volume stays sharded: batch over "data", planes over "plane". Falls
    back assertion-free only when S divides the plane axis; callers guard.
    Returns (rgb [B,3,H,W], depth [B,1,H,W]).
    """
    from mine_tpu.parallel.mesh import shard_map

    S = rgb_BS3HW.shape[1]
    n_plane = mesh.shape[PLANE_AXIS]
    assert S % n_plane == 0, (S, n_plane)

    body = functools.partial(_local_composite, z_mask=z_mask,
                             axis=PLANE_AXIS)
    vol = P(DATA_AXIS, PLANE_AXIS)
    f = shard_map(body, mesh=mesh,
                  in_specs=(vol, vol, vol),
                  out_specs=P(DATA_AXIS))
    out = f(rgb_BS3HW.astype(jnp.float32), sigma_BS1HW.astype(jnp.float32),
            xyz_BS3HW.astype(jnp.float32))
    from mine_tpu.ops.rendering import finalize_depth
    rgb_out = out[:, 0:3]
    depth_out = finalize_depth(out[:, 3:4], out[:, 4:5], is_bg_depth_inf)
    return rgb_out, depth_out
