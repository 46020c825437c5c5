"""Procedural multi-view scenes with exact geometry.

Purpose: deterministic training/eval data for tests and benchmarks without
real datasets (the reference has no equivalent — its smoke tests used the
author's local photos, operations/test_rendering.py:13). A ground-truth MPI
(textured layers at known disparities) is rendered into V camera poses with
the same verified renderer the model trains against, so a correctly wired
trainer can drive the loss toward zero (SURVEY.md section 7 build-order
step 2: "overfitting one synthetic scene").

Batch layout (the framework-wide contract, see SynthesisTrainer):
  src_img, tgt_img: [B, H, W, 3] float32 in [0, 1]  (NHWC for the encoder)
  K_src, K_tgt:     [B, 3, 3]
  G_src_tgt:        [B, 4, 4]   (tgt camera -> src camera, like the reference)
  pt3d_src, pt3d_tgt: [B, 3, N] camera-frame points of the view
(the reference's per-item dict, nerf_dataset.py:105-127, squeezed to L=1
supervision like synthesis_task.set_data:184-209).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp

from mine_tpu import geometry
from mine_tpu.ops import rendering


def _smooth_noise(rng: np.random.RandomState, h: int, w: int, c: int,
                  base: int = 8) -> np.ndarray:
    """Low-frequency texture in [0,1]: upsampled random grid."""
    small = rng.uniform(size=(base, base, c)).astype(np.float32)
    ys = np.linspace(0, base - 1, h)
    xs = np.linspace(0, base - 1, w)
    y0 = np.floor(ys).astype(int)
    x0 = np.floor(xs).astype(int)
    y1 = np.minimum(y0 + 1, base - 1)
    x1 = np.minimum(x0 + 1, base - 1)
    ty = (ys - y0)[:, None, None]
    tx = (xs - x0)[None, :, None]
    top = small[y0][:, x0] * (1 - tx) + small[y0][:, x1] * tx
    bot = small[y1][:, x0] * (1 - tx) + small[y1][:, x1] * tx
    return top * (1 - ty) + bot * ty


@jax.jit
def _render_views(mpi_rgb, mpi_sigma, disparity, K_33, G_V44):
    """The one canonical MPI [1,S,...] rendered into V poses, as ONE
    program (op by op this is ~100 small compiles per dataset, about a
    second each on the TPU). Returns (rgb [V,3,H,W], depth [V,H,W])."""
    V = G_V44.shape[0]
    K = jnp.broadcast_to(K_33, (V, 3, 3))
    K_inv = geometry.inverse_intrinsics(K)
    disp = jnp.broadcast_to(disparity, (V,) + disparity.shape[1:])
    res = rendering.render_tgt_rgb_depth(
        jnp.broadcast_to(mpi_rgb, (V,) + mpi_rgb.shape[1:]),
        jnp.broadcast_to(mpi_sigma, (V,) + mpi_sigma.shape[1:]),
        disp, G_V44, K_inv, K)
    return res.rgb, res.depth[:, 0]


class SyntheticMPIDataset:
    """V views of a fixed layered scene.

    The scene is an S_gt-plane MPI in the world frame: each plane has a
    low-frequency texture; densities make the nearest plane opaque in a
    blob region and transparent elsewhere, so views exhibit real parallax
    and dis-occlusion.
    """

    def __init__(self, seed: int = 0, height: int = 64, width: int = 64,
                 num_views: int = 6, num_planes_gt: int = 4,
                 num_points: int = 32, max_shift: float = 0.08):
        rng = np.random.RandomState(seed)
        H, W, S = height, width, num_planes_gt
        self.height, self.width = H, W
        self.num_points = num_points

        K = geometry.intrinsics_from_fov(H, W, fov_degrees=60.0)
        self.K = K

        # ground-truth MPI in the world(=plane) frame
        disparity = np.linspace(1.0, 0.2, S).astype(np.float32)  # depth 1..5
        rgb = np.stack([_smooth_noise(rng, H, W, 3) for _ in range(S)], axis=0)
        sigma = np.full((S, 1, H, W), 0.05, dtype=np.float32)
        # opaque blobs on the near planes (parallax + occlusion)
        yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
        for s in range(S - 1):
            cy, cx = rng.uniform(0.25, 0.75, 2) * [H, W]
            r = 0.18 * min(H, W) * rng.uniform(0.8, 1.4)
            blob = ((yy - cy) ** 2 + (xx - cx) ** 2) < r ** 2
            sigma[s, 0][blob] = 60.0
        sigma[S - 1] = 60.0  # far plane opaque background

        # rgb: [S,H,W,3] -> [1,S,3,H,W]
        self.mpi_rgb = jnp.asarray(rgb.transpose(0, 3, 1, 2))[None]
        self.mpi_sigma = jnp.asarray(sigma)[None]  # [1,S,1,H,W]
        self.disparity = jnp.asarray(disparity)[None]  # [1,S]

        # camera poses: world -> camera, small random motions
        self.G_cam_world: List[np.ndarray] = []
        for v in range(num_views):
            G = np.eye(4, dtype=np.float32)
            if v > 0:
                t = rng.uniform(-max_shift, max_shift, 3).astype(np.float32)
                t[2] *= 0.5
                angle = rng.uniform(-0.02, 0.02, 3)
                Rx = _rot(angle)
                G[:3, :3] = Rx
                G[:3, 3] = t
            self.G_cam_world.append(G)

        # render every view from the canonical MPI
        rgb_v, depth_v = _render_views(
            self.mpi_rgb, self.mpi_sigma, self.disparity, jnp.asarray(K),
            jnp.asarray(np.stack(self.G_cam_world)))
        self.images: List[np.ndarray] = [
            np.clip(img, 0.0, 1.0) for img in np.asarray(rgb_v)]  # [3,H,W]
        self.depths: List[np.ndarray] = list(np.asarray(depth_v))  # [H,W]

        # per-view camera-frame 3D points from rendered depth
        self.pt3d: List[np.ndarray] = []
        K_inv = np.linalg.inv(K)
        for v in range(num_views):
            px = rng.randint(2, W - 2, size=num_points)
            py = rng.randint(2, H - 2, size=num_points)
            z = self.depths[v][py, px]
            pix = np.stack([px, py, np.ones_like(px)], axis=0).astype(np.float32)
            xyz = (K_inv @ pix) * z[None, :]
            self.pt3d.append(xyz.astype(np.float32))

        self.num_views = num_views

    def pair_batch(self, pairs) -> Dict[str, np.ndarray]:
        """Build a batch from (src_view, tgt_view) index pairs."""
        b = {
            "src_img": [], "tgt_img": [], "K_src": [], "K_tgt": [],
            "G_src_tgt": [], "pt3d_src": [], "pt3d_tgt": [],
        }
        for i, j in pairs:
            G_src_tgt = self.G_cam_world[i] @ np.linalg.inv(self.G_cam_world[j])
            b["src_img"].append(self.images[i].transpose(1, 2, 0))  # HWC
            b["tgt_img"].append(self.images[j].transpose(1, 2, 0))
            b["K_src"].append(self.K)
            b["K_tgt"].append(self.K)
            b["G_src_tgt"].append(G_src_tgt.astype(np.float32))
            b["pt3d_src"].append(self.pt3d[i])
            b["pt3d_tgt"].append(self.pt3d[j])
        return {k: np.stack(v, axis=0) for k, v in b.items()}


def _rot(angles) -> np.ndarray:
    ax, ay, az = angles
    cx, sx = np.cos(ax), np.sin(ax)
    cy, sy = np.cos(ay), np.sin(ay)
    cz, sz = np.cos(az), np.sin(az)
    Rx = np.array([[1, 0, 0], [0, cx, -sx], [0, sx, cx]])
    Ry = np.array([[cy, 0, sy], [0, 1, 0], [-sy, 0, cy]])
    Rz = np.array([[cz, -sz, 0], [sz, cz, 0], [0, 0, 1]])
    return (Rz @ Ry @ Rx).astype(np.float32)


def make_batch(batch_size: int = 1, height: int = 64, width: int = 64,
               num_points: int = 32, seed: int = 0) -> Dict[str, np.ndarray]:
    """One fixed batch for benchmarks / smoke tests."""
    ds = SyntheticMPIDataset(seed=seed, height=height, width=width,
                             num_views=batch_size + 1, num_points=num_points)
    pairs = [(v, v + 1) for v in range(batch_size)]
    return ds.pair_batch(pairs)


class SyntheticPairDataset:
    """SyntheticMPIDataset behind the LLFFDataset batch_iterator contract.

    Lets every consumer of get_dataset (train_cli, eval_cli, TrainLoop) run
    without real data: `data.name: synthetic` in the config. Consecutive-view
    pairs play the role of (src, tgt) items; the geometry/points are exact,
    so losses and PSNR/SSIM behave like a real (tiny) scene.
    """

    def __init__(self, num_views: int = 6, num_points: int = 32,
                 height: int = 64, width: int = 64, seed: int = 0):
        self.ds = SyntheticMPIDataset(seed=seed, height=height, width=width,
                                      num_views=num_views,
                                      num_points=num_points)
        self.pairs = [(i, i + 1) for i in range(num_views - 1)]

    def __len__(self):
        return len(self.pairs)

    def _view_info(self, v: int) -> Dict:
        return {
            "img": self.ds.images[v].transpose(1, 2, 0),  # HWC
            "K": self.ds.K,
            "G_cam_world": self.ds.G_cam_world[v],
            "xyzs": self.ds.pt3d[v],
        }

    def get_pair(self, index: int, rng=None):
        i, j = self.pairs[index]
        src = self._view_info(i)
        tgt = self._view_info(j)
        tgt["G_src_tgt"] = (
            src["G_cam_world"]
            @ np.linalg.inv(tgt["G_cam_world"])).astype(np.float32)
        return src, tgt

    def batch_iterator(self, batch_size, shuffle, seed=0, epoch=0,
                       drop_last=True, shard_index=0, num_shards=1,
                       workers=0, prefetch_batches=2):
        from mine_tpu.data.common import iterate_pair_batches
        yield from iterate_pair_batches(
            len(self.pairs), self.get_pair, batch_size, shuffle, seed=seed,
            epoch=epoch, drop_last=drop_last, shard_index=shard_index,
            num_shards=num_shards, workers=workers,
            prefetch_batches=prefetch_batches)
