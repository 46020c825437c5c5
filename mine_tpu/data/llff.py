"""LLFF / COLMAP dataset — RAM-cached, host-sharded, fixed-shape batches.

Replaces input_pipelines/llff/nerf_dataset.py. Same data semantics:
  * scans scene dirs under root, loads each scene's COLMAP `sparse/0` model
    (nerf_dataset.py:61-65); images come from `images_{ratio}` (+`_val` for
    validation, :47-53)
  * caches every image in RAM at init, bicubic-resized to (img_w, img_h)
    (:79-81,133-136)
  * per image: G_cam_world from qvec/tvec (:143-148), K from SIMPLE_RADIAL
    params scaled by the true downsample ratio (:152-161), visible-3D-point
    camera coords and reprojected depths with P-matrix sign/norm handling
    (:164-194)
  * item = (src view, target views from the same scene): random targets for
    training, deterministic for validation (:197-234); a random fixed-size
    subset of visible 3D points per item (:118-126)

TPU-first differences:
  * explicit numpy RNG per item (reproducible; the reference uses the global
    `random` module, :118,204,229)
  * the batch iterator shards by example index across hosts — the
    DistributedSampler equivalent (train.py:83-87) — and emits the framework
    batch dict (fixed shapes, NHWC images) ready for the jitted train step
  * L=1 supervision is squeezed at batch level like set_data (:198-206)
"""

from __future__ import annotations

import os
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from mine_tpu import native
from mine_tpu.data import colmap


class LLFFDataset:
    def __init__(self,
                 root: str,
                 is_validation: bool,
                 img_size: Tuple[int, int],
                 supervision_count: int = 1,
                 visible_points_count: int = 256,
                 img_pre_downsample_ratio: Optional[float] = 7.875,
                 logger=None):
        self.img_w, self.img_h = img_size
        self.is_validation = is_validation
        self.visible_points_count = visible_points_count
        self.supervision_count = supervision_count

        if img_pre_downsample_ratio is None or img_pre_downsample_ratio <= 1:
            image_folder = "images"
            pre_ratio = 1.0
        else:
            image_folder = "images_" + str(img_pre_downsample_ratio)
            pre_ratio = float(img_pre_downsample_ratio)
        if is_validation:
            image_folder += "_val"

        self.infos: List[Dict] = []           # flat list of per-image items
        self.scene_of: List[str] = []
        self.scene_to_indices: Dict[str, List[int]] = {}

        # two-phase cache fill: collect every image path + its metadata
        # first, then decode through the threaded native batch loader
        # (mine_tpu.native; sequential PIL when not built) in bounded
        # chunks — peak RAM stays dataset + one chunk, and the decode also
        # reports each image's pre-resize size (no separate header probe)
        records = []  # (scene, img_path, item, camera, points3d)
        for scene_name in sorted(os.listdir(root)):
            scene_dir = os.path.join(root, scene_name)
            sparse = os.path.join(scene_dir, "sparse/0")
            if not os.path.isdir(sparse):
                continue
            cameras, images, points3d = colmap.read_model(sparse, ext=".bin")
            assert len(cameras) == 1, scene_name

            for img_id in sorted(images.keys()):
                item = images[img_id]
                img_path = os.path.join(scene_dir, image_folder, item.name)
                if not os.path.exists(img_path):
                    continue
                records.append((scene_name, img_path, item,
                                cameras[item.camera_id], points3d))

        CHUNK = 64
        for c0 in range(0, len(records), CHUNK):
            chunk = records[c0:c0 + CHUNK]
            imgs, dims = native.load_batch_rgb(
                [r[1] for r in chunk], (self.img_w, self.img_h),
                with_src_sizes=True)
            for (scene_name, img_path, item, camera, points3d), img, (w, h) \
                    in zip(chunk, imgs, dims):
                ratios = (w * pre_ratio / self.img_w,
                          h * pre_ratio / self.img_h)
                # copy: `img` is a view into the chunk batch — the cache
                # must not pin the whole chunk per kept image
                info = self._build_info(item, camera, points3d, img.copy(),
                                        ratios)
                if info is None:
                    continue
                assert info["xyzs"].shape[1] >= visible_points_count, (
                    f"{img_path}: {info['xyzs'].shape[1]} < "
                    f"{visible_points_count} visible points")
                idx = len(self.infos)
                self.infos.append(info)
                self.scene_of.append(scene_name)
                self.scene_to_indices.setdefault(scene_name, []).append(idx)

        if logger:
            logger.info("Dataset root: %s, is_validation: %s, images: %d",
                        root, is_validation, len(self.infos))

    # ---------------- per-image preprocessing ----------------

    @staticmethod
    def _build_info(img_item: colmap.Image, camera: colmap.Camera,
                    points3d, img: np.ndarray, ratios) -> Optional[Dict]:
        ratio_x, ratio_y = ratios

        R = colmap.qvec2rotmat(img_item.qvec).astype(np.float32)
        t = img_item.tvec.astype(np.float32)
        G_cam_world = np.eye(4, dtype=np.float32)
        G_cam_world[:3, :3] = R
        G_cam_world[:3, 3] = t

        # SIMPLE_RADIAL: params = (f, cx, cy, k); focal scaled per axis by the
        # true downsample ratio (nerf_dataset.py:152-161)
        K = np.array([[camera.params[0] / ratio_x, 0, camera.params[1] / ratio_x],
                      [0, camera.params[0] / ratio_y, camera.params[2] / ratio_y],
                      [0, 0, 1]], dtype=np.float32)

        tracked = img_item.point3D_ids != -1
        if tracked.sum() == 0:
            return None
        pids = img_item.point3D_ids[tracked]
        xys = img_item.xys[tracked].T.astype(np.float32)  # [2,N] original px
        xys = xys / np.array([[ratio_x], [ratio_y]], dtype=np.float32)
        xyz_world = np.stack([points3d[p].xyz for p in pids], axis=1)  # [3,N]

        # camera-frame coords + projective depths with sign/norm handling
        # (nerf_dataset.py:164-194)
        I0 = np.eye(3, 4, dtype=np.float32)
        P = K @ I0 @ G_cam_world
        det_sign = np.sign(np.linalg.det(P[:, :-1]))
        m3_norm = np.linalg.norm(P[2, :-1])

        xyz_world_h = np.concatenate(
            [xyz_world, np.ones((1, xyz_world.shape[1]), np.float32)], axis=0)
        xyz_cam_h = G_cam_world @ xyz_world_h.astype(np.float32)
        xyz_cam_h = xyz_cam_h / xyz_cam_h[-1:]
        reproj = K @ I0 @ xyz_cam_h
        depths = (det_sign * reproj[-1]) / m3_norm

        return {
            "img": np.ascontiguousarray(img),                # [H,W,3]
            "G_cam_world": G_cam_world,
            "K": K,
            "K_inv": np.linalg.inv(K).astype(np.float32),
            "xyzs": xyz_cam_h[:3].astype(np.float32),        # [3,N] camera frame
            "xyzs_ids": pids,
            "depths": depths.astype(np.float32),
        }

    # ---------------- item sampling ----------------

    def __len__(self) -> int:
        return len(self.infos)

    def get_item(self, index: int, rng: np.random.RandomState):
        """(src_item, [tgt_items]) with per-item point subsampling.

        Mirrors NeRFDataset.__getitem__ + _sample_tgt_items
        (nerf_dataset.py:105-127,197-234).
        """
        scene = self.scene_of[index]
        src = dict(self.infos[index])
        src = self._subsample_points(src, rng)

        indices = [i for i in self.scene_to_indices[scene] if i != index]
        if not self.is_validation:
            chosen = rng.choice(len(indices), size=self.supervision_count,
                                replace=False)
            chosen = [indices[c] for c in chosen]
        else:
            chosen = [indices[(index + 1) % len(indices) - 1]]

        G_src_world = src["G_cam_world"]
        tgts = []
        for j in chosen:
            tgt = dict(self.infos[j])
            tgt = self._subsample_points(tgt, rng)
            tgt["G_src_tgt"] = (
                G_src_world @ np.linalg.inv(tgt["G_cam_world"])).astype(np.float32)
            tgts.append(tgt)
        return src, tgts

    def _subsample_points(self, info: Dict, rng: np.random.RandomState) -> Dict:
        n = info["xyzs"].shape[1]
        sel = rng.choice(n, size=self.visible_points_count, replace=False)
        out = dict(info)
        out["xyzs"] = info["xyzs"][:, sel]
        out["xyzs_ids"] = info["xyzs_ids"][sel]
        out["depths"] = info["depths"][sel]
        return out

    # ---------------- batching ----------------

    def batch_iterator(self,
                       batch_size: int,
                       shuffle: bool,
                       seed: int = 0,
                       epoch: int = 0,
                       drop_last: bool = True,
                       shard_index: int = 0,
                       num_shards: int = 1,
                       workers: int = 0,
                       prefetch_batches: int = 2
                       ) -> Iterator[Dict[str, np.ndarray]]:
        """Fixed-shape framework batches, sharded across hosts by index.

        Equivalent to DistributedSampler(set_epoch) + DataLoader + collate +
        set_data's L=1 squeeze (train.py:83-87, synthesis_task.py:184-209).
        """
        from mine_tpu.data.common import iterate_pair_batches

        def get_pair(idx, rng):
            src, tgts = self.get_item(idx, rng)
            return src, tgts[0]

        yield from iterate_pair_batches(
            len(self.infos), get_pair, batch_size, shuffle, seed=seed,
            epoch=epoch, drop_last=drop_last, shard_index=shard_index,
            num_shards=num_shards, workers=workers,
            prefetch_batches=prefetch_batches)


def get_dataset(config: Dict, logger=None) -> Tuple[LLFFDataset, LLFFDataset]:
    """Build (train, val) datasets per config — the reference's get_dataset
    (train.py:69-103). Only the LLFF/COLMAP loader exists upstream; other
    dataset names raise NotImplementedError there too (train.py:100-101)."""
    name = config["data.name"]
    if name == "packed_tokens":
        # the looped language model's feed (model.family: looplm); no
        # validation set
        from mine_tpu.data.tokens import dataset_from_config
        return dataset_from_config(config,
                                   seed=int(config.get("training.seed", 0))
                                   ), None
    if name == "synthetic":
        # procedural scene, no files needed: smoke-tests the full
        # train/eval/CLI stack (mine_tpu.data.synthetic)
        from mine_tpu.data.synthetic import SyntheticPairDataset
        mk = lambda seed: SyntheticPairDataset(  # noqa: E731
            num_views=int(config.get("data.num_seq_per_gpu", 4)) + 2,
            num_points=int(config.get("data.visible_point_count", 256)),
            height=int(config["data.img_h"]),
            width=int(config["data.img_w"]),
            seed=seed)
        return mk(0), mk(1)
    if name == "realestate10k":
        # capability beyond the reference (its get_dataset raises for
        # everything but llff, train.py:100-101) — see data/realestate10k.py
        from mine_tpu.data.realestate10k import RealEstate10KDataset
        common = dict(
            img_size=(config["data.img_w"], config["data.img_h"]),
            # default matches mpi_config_from_dict (256): a missing key must
            # not silently pair dummy points with an enabled disparity loss
            visible_points_count=config.get("data.visible_point_count", 256),
            frames_apart=config.get("testing.frames_apart", "random"),
            max_frame_gap=config.get("data.max_frame_gap", 30),
            points_root=config.get("data.points_root"),
            logger=logger)
        train = RealEstate10KDataset(
            root=config["data.training_set_path"],
            is_validation=False, **common)
        val = RealEstate10KDataset(
            root=config["data.val_set_path"],
            is_validation=True,
            pairs_json=config.get("data.val_pairs_json"),
            tgt_key=config.get("data.val_pairs_tgt", "tgt_img_obj_5_frames"),
            **common)
        return train, val
    if name == "flowers":
        # capability beyond the reference: consumes its shipped calibration
        # assets (input_pipelines/flowers/) — see data/flowers.py
        from mine_tpu.data.flowers import FlowersDataset
        common = dict(
            img_size=(config["data.img_w"], config["data.img_h"]),
            cam_params_path=config.get("data.cam_params_path"),
            grid=config.get("data.lenslet_grid", 8),
            lenslet_stride=config.get("data.lenslet_stride", 14),
            logger=logger)
        train = FlowersDataset(root=config["data.training_set_path"],
                               is_validation=False, **common)
        val = FlowersDataset(root=config["data.val_set_path"],
                             is_validation=True, **common)
        return train, val
    if name == "kitti_raw":
        # capability beyond the reference: rectified stereo pairs from the
        # public KITTI raw layout — see data/kitti.py
        from mine_tpu.data.kitti import KITTIRawDataset
        sz = (config["data.img_w"], config["data.img_h"])
        train = KITTIRawDataset(root=config["data.training_set_path"],
                                is_validation=False, img_size=sz,
                                logger=logger)
        val = KITTIRawDataset(root=config["data.val_set_path"],
                              is_validation=True, img_size=sz, logger=logger)
        return train, val
    if name == "dtu":
        # capability beyond the reference: MVSNet-preprocessed DTU layout,
        # honoring its dtu-only config keys — see data/dtu.py
        from mine_tpu.data.dtu import DTUDataset
        common = dict(
            img_size=(config["data.img_w"], config["data.img_h"]),
            rotation_pi_ratio=float(config.get("data.rotation_pi_ratio", 3)),
            is_exclude_views=bool(config.get("data.is_exclude_views", False)),
            intrinsics_scale=float(
                config.get("data.dtu_intrinsics_scale", 4) or 4),
            logger=logger)
        train = DTUDataset(root=config["data.training_set_path"],
                           is_validation=False, **common)
        val = DTUDataset(root=config["data.val_set_path"],
                         is_validation=True, **common)
        return train, val
    if name != "llff":
        raise NotImplementedError(
            f"dataset '{name}': unknown dataset name (the reference itself "
            f"ships only the LLFF loader, train.py:100-101; this framework "
            f"adds realestate10k/kitti_raw/flowers/dtu/synthetic)")
    train = LLFFDataset(
        root=config["data.training_set_path"],
        is_validation=False,
        img_size=(config["data.img_w"], config["data.img_h"]),
        supervision_count=config.get("data.num_tgt_views", 1),
        visible_points_count=config.get("data.visible_point_count", 256),
        img_pre_downsample_ratio=config.get("data.img_pre_downsample_ratio"),
        logger=logger)
    val = LLFFDataset(
        root=config["data.training_set_path"],
        is_validation=True,
        img_size=(config["data.img_w"], config["data.img_h"]),
        supervision_count=config.get("data.num_tgt_views", 1),
        visible_points_count=config.get("data.visible_point_count", 256),
        img_pre_downsample_ratio=config.get("data.img_pre_downsample_ratio"),
        logger=logger)
    return train, val
