"""Single-image inference -> novel-view camera-path videos.

Replaces visualizations/image_to_video.py: encode ONE image into an MPI, then
render a camera trajectory by re-running only the warp+composite per pose
(VideoGenerator: infer once :112-153, render per frame :219-255).

TPU-first difference: poses are rendered in jitted *batches* (the pose axis is
just a batch axis of the warp), not one python-loop frame at a time. The
batched render itself lives in the serving engine (mine_tpu/serve): this
class encodes the image, caches the blended MPI in the engine's cache, and
drives `RenderEngine.render` per trajectory — the same compile-once,
render-only program the serving path uses. The default float32 cache keeps
frames bitwise-identical to the pre-engine private chunk loop
(tests/test_serve.py gates this).

Videos are written with imageio(+ffmpeg) when available, else PNG frames —
moviepy (the reference's writer) is not in this image.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from mine_tpu import geometry
from mine_tpu.config import mpi_config_from_dict, validate_model_shapes
from mine_tpu.models.mpi import MPIPredictor
from mine_tpu.ops import rendering
from mine_tpu.serve import (ContinuousBatcher, MPICache, RenderEngine,
                            SessionManager, image_id_for)
from mine_tpu.train.step import sample_disparity
from mine_tpu.utils import disparity_normalization_vis

_log = logging.getLogger(__name__)


def path_planning(num_frames: int, x: float, y: float, z: float,
                  path_type: str = "", s: float = 0.3):
    """Camera path generators (reference image_to_video.py:22-48):
    'straight-line' (quadratic through origin/mid/end), 'double-straight-line'
    (linear there-and-back), 'circle'."""
    if path_type == "straight-line":
        corner_points = np.array([[0, 0, 0],
                                  [(0 + x) * 0.5, (0 + y) * 0.5, (0 + z) * 0.5],
                                  [x, y, z]])
        t = np.linspace(0, 1, num_frames)
        # quadratic through the 3 corner points (t = 0, .5, 1)
        coeffs = np.polyfit(np.linspace(0, 1, 3), corner_points, 2)  # [3,3dims]
        spline = np.stack([np.polyval(coeffs[:, i], t) for i in range(3)], axis=1)
        xs, ys, zs = spline[:, 0], spline[:, 1], spline[:, 2]
    elif path_type == "double-straight-line":
        t = np.linspace(0, 1, int(num_frames * 0.5))
        start = np.array([s * x, s * y, s * z])
        end = np.array([-x, -y, -z])
        seg = start[None] * (1 - t[:, None]) + end[None] * t[:, None]
        xs = np.concatenate([seg[:, 0], np.flip(seg[:, 0])])
        ys = np.concatenate([seg[:, 1], np.flip(seg[:, 1])])
        zs = np.concatenate([seg[:, 2], np.flip(seg[:, 2])])
    elif path_type == "circle":
        xs, ys, zs = [], [], []
        for shift in np.arange(-2.0, 2.0, 4.0 / num_frames):
            xs.append(np.cos(shift * np.pi) * x)
            ys.append(np.sin(shift * np.pi) * y)
            zs.append(np.cos(shift * np.pi / 2.0) * z - s * z)
        xs, ys, zs = np.array(xs), np.array(ys), np.array(zs)
    else:
        raise ValueError(f"unknown path_type {path_type}")
    return xs, ys, zs


# band height of the Pallas warp gather (kernels/warp.py); poses whose
# row-block span (+ bilinear support + the kernel's sublane-alignment
# slack) exceeds it fall back to the XLA gather. 32 (was 16): the round-4
# alignment slack costs 7 rows of headroom, and forward-only banded cost
# scales only linearly with the band.
WARP_BAND = 32

TRAJECTORY_PRESETS = {
    # dataset -> (fps, num_frames, x_ranges, y_ranges, z_ranges, types, names)
    # (reference image_to_video.py:156-175)
    "kitti_raw": (30, 90, [0.0, -0.8], [0.0, -0.0], [-1.5, -1.0],
                  ["double-straight-line", "circle"], ["zoom-in", "swing"]),
    "realestate10k": (30, 90, [0.0, -0.16], [0.0, -0.0], [-0.30, -0.2],
                      ["double-straight-line", "circle"], ["zoom-in", "swing"]),
    "nyu": (30, 90, [0.0, -0.16], [0.0, -0.0], [-0.30, -0.2],
            ["double-straight-line", "circle"], ["zoom-in", "swing"]),
    "ibims": (30, 90, [0.0, -0.16], [0.0, -0.0], [-0.30, -0.2],
              ["double-straight-line", "circle"], ["zoom-in", "swing"]),
    # fallback used for llff/flowers/dtu (not covered upstream)
    "_default": (30, 60, [0.0, -0.12], [0.0, -0.0], [-0.24, -0.16],
                 ["double-straight-line", "circle"], ["zoom-in", "swing"]),
}


def generate_trajectories(dataset_name: str):
    preset = TRAJECTORY_PRESETS.get(dataset_name, TRAJECTORY_PRESETS["_default"])
    fps, num_frames, xr, yr, zr, types, names = preset
    trajectories = []
    for i, ttype in enumerate(types):
        sx, sy, sz = path_planning(num_frames, xr[i], yr[i], zr[i],
                                   path_type=ttype)
        poses = []
        for xx, yy, zz in zip(sx, sy, sz):
            G = np.eye(4, dtype=np.float32)
            G[:3, 3] = [xx, yy, zz]
            poses.append(G)
        trajectories.append(np.stack(poses))  # [F,4,4]
    return trajectories, {"fps": fps, "names": names}


def _blend_mpi(cfg, backend: str, mpi, img_1hw3, disparity, K_inv):
    """Source-blend the predicted MPI (the reference infer_network tail):
    render the blend weights at the source pose and mix the source pixels
    into the plane RGB. One code path shared by the single-image
    VideoGenerator and the per-frame streaming encode (StreamRenderer), so
    both produce bitwise-identical planes for the same pixels."""
    rgb = mpi[:, :, 0:3]
    sigma = mpi[:, :, 3:4]
    H, W = int(img_1hw3.shape[1]), int(img_1hw3.shape[2])
    grid = geometry.cached_pixel_grid(H, W)
    xyz_src = geometry.plane_xyz_src(grid, disparity, K_inv)
    src_nchw = jnp.transpose(img_1hw3, (0, 3, 1, 2))
    if backend == "pallas" and not cfg.use_alpha:
        # one fused pass: composite + src rgb blending + blended volume
        from mine_tpu.kernels import on_tpu_backend
        from mine_tpu.kernels.composite import fused_src_render_blend
        _, _, mpi_rgb = fused_src_render_blend(
            rgb, sigma, xyz_src, src_nchw,
            is_bg_depth_inf=cfg.is_bg_depth_inf,
            interpret=not on_tpu_backend())
    else:
        _, _, blend_weights, _ = rendering.render(
            rgb, sigma, xyz_src,
            use_alpha=cfg.use_alpha,
            is_bg_depth_inf=cfg.is_bg_depth_inf)
        mpi_rgb = blend_weights * src_nchw[:, None] + \
            (1.0 - blend_weights) * rgb
    return mpi_rgb, sigma


@jax.jit
def _src_from_tgt_homographies(K_33, K_inv_33, poses_F44, depths_S):
    """[F,S,3,3] target->source homographies: one source of truth, the
    same composition the device warp uses (geometry.homography_tgt_src),
    batched over [F,S]; one program, not one per op."""
    F, S = poses_F44.shape[0], depths_S.shape[0]
    Hts = geometry.homography_tgt_src(
        jnp.broadcast_to(K_33, (F, S, 3, 3)),
        jnp.broadcast_to(K_inv_33, (F, S, 3, 3)),
        jnp.broadcast_to(poses_F44[:, None], (F, S, 4, 4)),
        jnp.broadcast_to(depths_S[None, :], (F, S)))
    return geometry.inverse_3x3(Hts)


@functools.lru_cache(maxsize=8)
def _encode_program(model, cfg, backend: str):
    """Network pass + source blend as ONE jitted program per (model,
    config, backend), shared by every image of a run. Op by op, the same
    ResNet-50 encode is ~600 small programs, and on the TPU each costs its
    own compile of about a second (chip run, PR 24: the serve child spent
    376 s compiling and never reached a render)."""
    def encode(variables, img_1hw3, disparity, K_inv):
        mpi = model.apply(variables, img_1hw3, disparity, train=False)[0]
        return _blend_mpi(cfg, backend, mpi, img_1hw3, disparity, K_inv)
    return jax.jit(encode)


class VideoGenerator:
    """Encode one image, then render trajectories in jitted pose chunks."""

    def __init__(self, config: Dict, params, batch_stats,
                 img_hwc: np.ndarray,
                 chunk: int = 8,
                 dtype=jnp.bfloat16,
                 seed: int = 0,
                 backend: Optional[str] = None,
                 engine: Optional[RenderEngine] = None,
                 cache_quant: str = "float32",
                 encoder_quant: str = "off"):
        self.cfg = mpi_config_from_dict(config)
        validate_model_shapes(self.cfg)
        self.config = config
        self.chunk = chunk
        if backend is None:
            # fused Pallas composite on TPU-class backends, XLA elsewhere
            from mine_tpu.kernels import on_tpu_backend
            backend = "pallas" if on_tpu_backend() else "xla"
        self.backend = backend
        H, W = self.cfg.img_h, self.cfg.img_w

        img = _resize_bilinear(img_hwc, H, W)
        self.img = jnp.asarray(img, jnp.float32)[None]  # [1,H,W,3]

        self.K = jnp.asarray(geometry.intrinsics_from_fov(H, W, 90.0))[None]
        self.K_inv = geometry.inverse_intrinsics(self.K)
        self.image_id = image_id_for(np.asarray(self.img))
        self.disparity = sample_disparity(jax.random.PRNGKey(seed), 1,
                                          self.cfg)

        # a shared engine that already holds this image's planes serves it
        # render-only: the encoder does not run again (serve_cli.py)
        self.engine = engine
        self.encoded = engine is None or self.image_id not in engine.cache
        if not self.encoded:
            return

        model = MPIPredictor(
            num_layers=self.cfg.num_layers,
            pos_encoding_multires=self.cfg.pos_encoding_multires,
            use_alpha=self.cfg.use_alpha,
            dtype=dtype)

        # one network pass (reference infer_network :112-153)
        if encoder_quant == "off":
            variables = {"params": params, "batch_stats": batch_stats}
            self.mpi_rgb, self.mpi_sigma = _encode_program(
                model, self.cfg, self.backend)(
                    variables, self.img, self.disparity, self.K_inv)
        else:
            # serve.encoder_quant=int8: weights stored per-channel int8 with
            # the widening dequant fused into the jitted encode
            # (mine_tpu/serve/encoder.py); a pre-quantized params tree
            # (serve_cli quantizes once for all images) passes through
            from mine_tpu.serve.encoder import make_encode_fn
            encode = make_encode_fn(model, params, batch_stats,
                                    encoder_quant=encoder_quant)
            mpi = encode(self.img, self.disparity)
            self.mpi_rgb, self.mpi_sigma = _blend_mpi(
                self.cfg, self.backend, mpi, self.img, self.disparity,
                self.K_inv)

        # hand the encode to the serving engine's cache; trajectories render
        # through its bucketed jitted program (one compile set per warp impl)
        if engine is None:
            self.engine = engine = RenderEngine(
                use_alpha=self.cfg.use_alpha,
                is_bg_depth_inf=self.cfg.is_bg_depth_inf,
                backend=self.backend,
                warp_band=WARP_BAND,
                max_bucket=chunk,
                cache=MPICache(quant=cache_quant))
        engine.put(self.image_id, self.mpi_rgb[0], self.mpi_sigma[0],
                   self.disparity[0], self.K[0])

    def _max_row_block_span(self, poses_F44: np.ndarray,
                            rows_per_block: int = 8, step: int = 8) -> float:
        """Host-side (numpy) upper estimate of the per-row-block source-row
        span of the warp, over all poses and planes — decides whether the
        banded Pallas gather's correctness domain holds for a trajectory
        (kernels/warp.py module docstring)."""
        H, W = self.cfg.img_h, self.cfg.img_w
        F = poses_F44.shape[0]
        depths = 1.0 / np.asarray(self.disparity[0])  # [S]
        S = depths.shape[0]

        Hst = np.asarray(_src_from_tgt_homographies(
            self.K[0], self.K_inv[0], jnp.asarray(poses_F44),
            jnp.asarray(depths)))                            # [F,S,3,3]

        # block-boundary rows x coarse columns
        rows = np.stack([np.arange(0, H, rows_per_block),
                         np.arange(0, H, rows_per_block) + rows_per_block - 1],
                        axis=1).reshape(-1).astype(np.float32)  # [2*NB]
        cols = np.arange(0, W, step, dtype=np.float32)
        ii, jj = np.meshgrid(rows, cols, indexing="ij")      # [NR,NJ]
        pts = np.stack([jj, ii, np.ones_like(ii)], axis=0)   # [3,NR,NJ]

        num = np.einsum("fsab,brj->fsarj", Hst, pts)         # [F,S,3,NR,NJ]
        y = num[:, :, 1] / num[:, :, 2]                      # [F,S,NR,NJ]
        y = np.clip(y, 0.0, H - 1.0)
        yb = y.reshape(y.shape[0], y.shape[1], -1, 2, y.shape[-1])  # per block
        span = yb.max(axis=(3, 4)) - yb.min(axis=(3, 4))
        return float(span.max())

    def render_poses(self, poses_F44: np.ndarray):
        """[F,4,4] -> (rgb [F,3,H,W], disparity [F,1,H,W]) numpy."""
        warp_impl = "xla"
        if self.backend == "pallas" and self.cfg.img_h % 8 == 0:
            # banded Pallas gather only when the trajectory's warp fits the
            # band: span + 2 rows of bilinear support + the kernel's
            # sublane-alignment slack (kernels/warp.py _align_slack — the
            # floored band start can sit up to 7 rows above the ideal one),
            # + 2 extra margin for the coarse span estimate
            from mine_tpu.kernels.warp import _align_slack
            span = self._max_row_block_span(poses_F44)
            slack = _align_slack(WARP_BAND, int(self.cfg.img_h))
            if span + 4 + slack <= WARP_BAND:
                warp_impl = "pallas"
        self.last_warp_impl = warp_impl
        rgb, depth = self.engine.render(
            self.image_id, np.asarray(poses_F44, np.float32),
            warp_impl=warp_impl)
        # floor matches the loss graph's safe inversion: fully-transparent
        # pixels composite to depth 0 and would otherwise make inf frames
        return rgb, np.float32(1.0) / np.maximum(depth, np.float32(1e-8))

    def render_videos(self, output_dir: str, output_name: str) -> List[str]:
        trajectories, meta = generate_trajectories(self.config.get("data.name",
                                                                   "_default"))
        os.makedirs(output_dir, exist_ok=True)
        written = []
        for poses, name in zip(trajectories, meta["names"]):
            rgb, disp = self.render_poses(poses)
            _log.info("views %s_%s: warp=%s n=%d finite=%s rgb_min=%.4f "
                      "rgb_max=%.4f rgb_std=%.4f", output_name, name,
                      self.last_warp_impl, rgb.shape[0],
                      bool(np.isfinite(rgb).all() and np.isfinite(disp).all()),
                      rgb.min(), rgb.max(), rgb.std())
            disp_vis = disparity_normalization_vis(disp)
            rgb_u8 = _to_uint8_frames(rgb)
            disp_u8 = _colormap_frames(disp_vis)
            for frames, tag in ((rgb_u8, "rgb"), (disp_u8, "disp")):
                path = os.path.join(output_dir,
                                    f"{output_name}_{name}_{tag}")
                written.append(_write_video(frames, path, meta["fps"]))
        return written


class StreamRenderer:
    """Keyframe-cadenced streaming video over the serving session plane.

    Where `VideoGenerator` encodes ONE image and renders a trajectory from
    it, this drives a live frame sequence through a `StreamSession`
    (mine_tpu/serve/session.py): the network runs only at keyframes (every
    `keyframe_every` frames, or earlier when the drift proxy trips), and
    every other frame is warp+composite from its keyframe's cached MPI —
    through the SAME bucketed jitted render program and AOT store static
    serving uses, so streaming adds no compile surface.

    `keyframe_every=1` degenerates to encode-every-frame and is bitwise
    identical to the per-frame `VideoGenerator` path (the K=1 parity test
    in tests/test_stream_session.py pins this).

    Pass `manager=` to ride an existing serving backend (a `ServeFleet`'s
    SessionManager); by default the renderer owns a private
    RenderEngine + ContinuousBatcher + SessionManager and closes them.
    """

    def __init__(self, config: Dict, params, batch_stats,
                 chunk: int = 8,
                 dtype=jnp.bfloat16,
                 seed: int = 0,
                 backend: Optional[str] = None,
                 manager: Optional[SessionManager] = None,
                 cache_quant: str = "float32",
                 encoder_quant: str = "off",
                 keyframe_every: int = 1,
                 drift_budget: float = 0.0,
                 drift_mode: str = "probe",
                 probe_stride: int = 4,
                 max_wait_ms: float = 2.0):
        self.cfg = mpi_config_from_dict(config)
        validate_model_shapes(self.cfg)
        self.config = config
        if backend is None:
            from mine_tpu.kernels import on_tpu_backend
            backend = "pallas" if on_tpu_backend() else "xla"
        self.backend = backend
        H, W = self.cfg.img_h, self.cfg.img_w

        self.K = jnp.asarray(geometry.intrinsics_from_fov(H, W, 90.0))[None]
        self.K_inv = geometry.inverse_intrinsics(self.K)

        model = MPIPredictor(
            num_layers=self.cfg.num_layers,
            pos_encoding_multires=self.cfg.pos_encoding_multires,
            use_alpha=self.cfg.use_alpha,
            dtype=dtype)
        # one fixed disparity set for the whole stream (same sampling the
        # single-image path uses) — keyframes share plane geometry, so the
        # render program's disparity input never changes shape or value
        self.disparity = sample_disparity(jax.random.PRNGKey(seed), 1,
                                          self.cfg)
        if encoder_quant == "off":
            variables = {"params": params, "batch_stats": batch_stats}
            program = _encode_program(model, self.cfg, self.backend)

            def _encode(img_1hw3):
                return program(variables, img_1hw3, self.disparity,
                               self.K_inv)
        else:
            from mine_tpu.serve.encoder import make_encode_fn
            encode = make_encode_fn(model, params, batch_stats,
                                    encoder_quant=encoder_quant)

            def _encode(img_1hw3):
                return _blend_mpi(self.cfg, self.backend,
                                  encode(img_1hw3, self.disparity),
                                  img_1hw3, self.disparity, self.K_inv)

        def _encode_frame(img_hwc):
            """engine encode_fn: full network pass + source blend for ONE
            observed frame — the keyframe path (the same program as
            VideoGenerator.__init__)."""
            img = jnp.asarray(img_hwc, jnp.float32)[None]
            mpi_rgb, mpi_sigma = _encode(img)
            return (mpi_rgb[0], mpi_sigma[0], self.disparity[0], self.K[0])

        self.encode_frame = _encode_frame
        self._owned_batcher = None
        if manager is None:
            engine = RenderEngine(
                use_alpha=self.cfg.use_alpha,
                is_bg_depth_inf=self.cfg.is_bg_depth_inf,
                backend=self.backend,
                warp_band=WARP_BAND,
                max_bucket=chunk,
                cache=MPICache(quant=cache_quant),
                encode_fn=_encode_frame)
            self._owned_batcher = ContinuousBatcher(engine,
                                                    max_requests=chunk,
                                                    max_wait_ms=max_wait_ms)
            manager = SessionManager(self._owned_batcher,
                                     keyframe_every=keyframe_every,
                                     drift_budget=drift_budget,
                                     drift_mode=drift_mode,
                                     probe_stride=probe_stride)
        self.manager = manager
        self.last_stats: Optional[dict] = None

    def prepare_frame(self, frame_hwc: np.ndarray) -> np.ndarray:
        """Resize/normalize one observed frame to the model's [H,W,3] f32."""
        return np.asarray(
            _resize_bilinear(frame_hwc, self.cfg.img_h, self.cfg.img_w),
            np.float32)

    def stream(self, frames, poses_F44: Optional[np.ndarray] = None,
               session_id: Optional[str] = None):
        """Drive a frame sequence through one session; returns
        (rgb [F,3,H,W], disparity [F,1,H,W]) f32 numpy in frame order.
        `poses_F44` are per-frame camera poses relative to the stream's
        world (default: identity — re-render each observed viewpoint)."""
        session = self.manager.open(session_id)
        futures = []
        try:
            for n, frame in enumerate(frames):
                prepared = self.prepare_frame(np.asarray(frame))
                pose = None if poses_F44 is None else \
                    np.asarray(poses_F44[n], np.float32)
                futures.append(session.process_frame(prepared, pose))
            results = [f.result() for f in futures]
        finally:
            self.last_stats = session.stats()
            session.close()
        rgb = np.stack([r[0] for r in results])
        depth = np.stack([r[1] for r in results])
        return rgb, np.float32(1.0) / np.maximum(depth, np.float32(1e-8))

    def close(self) -> None:
        self.manager.close()
        if self._owned_batcher is not None:
            self._owned_batcher.close()


# ---------------- image helpers ----------------

def _resize_bilinear(img_hwc: np.ndarray, H: int, W: int) -> np.ndarray:
    import cv2
    img = cv2.resize(img_hwc, (W, H), interpolation=cv2.INTER_LINEAR)
    if img.dtype == np.uint8:
        img = img.astype(np.float32) / 255.0
    return img


def _to_uint8_frames(rgb_f3hw: np.ndarray) -> np.ndarray:
    x = np.clip(np.round(rgb_f3hw * 255.0), 0, 255).astype(np.uint8)
    return np.transpose(x, (0, 2, 3, 1))  # [F,H,W,3]


def _colormap_frames(disp_f1hw: np.ndarray) -> np.ndarray:
    import cv2
    frames = []
    for d in disp_f1hw[:, 0]:
        u8 = np.clip(np.round(d * 255.0), 0, 255).astype(np.uint8)
        c = cv2.applyColorMap(u8, cv2.COLORMAP_HOT)
        frames.append(cv2.cvtColor(c, cv2.COLOR_BGR2RGB))
    return np.stack(frames)


def _write_video(frames_fhwc: np.ndarray, path_base: str, fps: int) -> str:
    """mp4 via imageio/ffmpeg; PNG frame directory as fallback."""
    try:
        import imageio
        path = path_base + ".mp4"
        imageio.mimwrite(path, list(frames_fhwc), fps=fps)
        return path
    except Exception:
        os.makedirs(path_base, exist_ok=True)
        from PIL import Image as PILImage
        for i, f in enumerate(frames_fhwc):
            PILImage.fromarray(f).save(
                os.path.join(path_base, f"frame_{i:04d}.png"))
        return path_base
