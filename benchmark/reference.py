"""Plain reference of the render-only call: cached planes + pose -> view.

Straightforward float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`, written from SURVEY.md's
equations (MINE, mpi_rendering.py / homography_sampler.py): per-plane
homography, bilinear gather with border clamping, density over-compositing.
No kernels, no batching, no cache; it imports nothing from `mine_tpu`. Both
configurations of the benchmark share it, as they share every line of the
program's warp and composite code.

  plane s lies at depth d_s = 1 / disparity_s in the source frame, normal
  n = (0, 0, 1).  With G = [R | t] taking source points to the target frame,
    H_tgt_src = K (R + t n^T / d_s) K^-1            (source px -> target px)
  a target pixel p samples the source at q = H_tgt_src^-1 p (bilinear, q
  clamped to the image: grid_sample(border)).  The sampled plane point, in
  the target frame, is X = R (K^-1 [q_x, q_y, 1] d_s) + t; density counts
  only where X_z >= 0.  Along the ray, dist_s = |X_{s+1} - X_s| (1e3 behind
  the last plane), T_s = exp(-sigma_s dist_s), and
    w_s = (1 - T_s) prod_{j<s} (T_j + 1e-6)
    rgb = sum_s w_s rgb_s,   depth = sum_s w_s X_s,z / (sum_s w_s + 1e-5).

One departure from a literal reading of the reference, noted: the program
warps the plane points X as three more channels of the volume, bilinearly;
X is affine in q, so inside the image that interpolation is exact, and here
X is evaluated in closed form at the clamped q instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _bilinear_border(img_chw, x, y):
    """img [C,H,W] sampled at continuous pixel coords x, y [H,W], clamped
    to the pixel-centre box."""
    _, h, w = img_chw.shape
    x = jnp.clip(x, 0.0, w - 1.0)
    y = jnp.clip(y, 0.0, h - 1.0)
    x0 = jnp.floor(x)
    y0 = jnp.floor(y)
    tx, ty = x - x0, y - y0
    x0 = x0.astype(jnp.int32)
    y0 = y0.astype(jnp.int32)
    x1 = jnp.minimum(x0 + 1, w - 1)
    y1 = jnp.minimum(y0 + 1, h - 1)
    top = img_chw[:, y0, x0] * (1.0 - tx) + img_chw[:, y0, x1] * tx
    bot = img_chw[:, y1, x0] * (1.0 - tx) + img_chw[:, y1, x1] * tx
    return top * (1.0 - ty) + bot * ty, x, y


@functools.partial(jax.jit, static_argnames=("is_bg_depth_inf",))
def render_view(planes_S4HW, disparity_S, K_33, G_tgt_src_44,
                is_bg_depth_inf: bool = False):
    """-> (rgb [3,H,W], depth [1,H,W]) of one view, float32."""
    with jax.default_matmul_precision("highest"):
        planes = planes_S4HW.astype(jnp.float32)
        s_count, _, h, w = planes.shape
        depth_S = 1.0 / disparity_S.astype(jnp.float32)
        K = K_33.astype(jnp.float32)
        K_inv = jnp.linalg.inv(K)
        R = G_tgt_src_44[:3, :3].astype(jnp.float32)
        t = G_tgt_src_44[:3, 3].astype(jnp.float32)
        n = jnp.asarray([0.0, 0.0, 1.0], jnp.float32)
        px, py = jnp.meshgrid(jnp.arange(w, dtype=jnp.float32),
                              jnp.arange(h, dtype=jnp.float32))
        tgt = jnp.stack([px, py, jnp.ones_like(px)]).reshape(3, h * w)

        def one_plane(plane_4hw, d):
            H_ts = K @ (R + jnp.outer(t, n) / d) @ K_inv
            q = jnp.linalg.inv(H_ts) @ tgt
            qx = (q[0] / q[2]).reshape(h, w)
            qy = (q[1] / q[2]).reshape(h, w)
            vals, cx, cy = _bilinear_border(plane_4hw, qx, qy)
            rays = K_inv @ jnp.stack([cx.ravel(), cy.ravel(),
                                      jnp.ones(h * w, jnp.float32)])
            X = (R @ (rays * d) + t[:, None]).reshape(3, h, w)
            return vals[0:3], vals[3], X

        rgb, sigma, X = jax.vmap(one_plane)(planes, depth_S)  # over planes
        sigma = jnp.where(X[:, 2] >= 0.0, sigma, 0.0)            # [S,H,W]
        dist = jnp.sqrt(jnp.sum((X[1:] - X[:-1]) ** 2, axis=1))  # [S-1,H,W]
        dist = jnp.concatenate([dist, jnp.full((1, h, w), 1e3, jnp.float32)])
        T = jnp.exp(-sigma * dist)
        acc = jnp.cumprod(T + 1e-6, axis=0)
        acc = jnp.concatenate([jnp.ones((1, h, w), jnp.float32), acc[:-1]])
        weights = acc * (1.0 - T)                                # [S,H,W]
        out_rgb = jnp.sum(weights[:, None] * rgb, axis=0)
        w_sum = jnp.sum(weights, axis=0)
        z_acc = jnp.sum(weights * X[:, 2], axis=0)
        if is_bg_depth_inf:
            depth = z_acc + (1.0 - w_sum) * 1000.0
        else:
            depth = z_acc / (w_sum + 1e-5)
        del s_count
        return out_rgb, depth[None]
