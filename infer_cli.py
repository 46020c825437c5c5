#!/usr/bin/env python
"""Single-image novel-view video inference — CLI-compatible with the
reference's visualizations/image_to_video.py.

  python infer_cli.py --checkpoint_path ws/v1/checkpoint_latest \
      --data_path photo.jpg --output_dir out/

Reads params.yaml next to the checkpoint (reference image_to_video.py:273-278).
Accepts either an orbax TrainState checkpoint directory or a converted .npz
weights file (tools/convert_torch_weights.py, including converted MINE
releases). --gpus is accepted for CLI parity and ignored (device selection is
JAX's).

--stream switches to streaming-session mode (mine_tpu/serve/session.py):
--data_path is then a DIRECTORY of frames (sorted by name) or a video file,
and the network runs only at keyframes — every --keyframe_every frames, or
earlier when the drift proxy exceeds --drift_budget:

  python infer_cli.py --checkpoint_path ws/v1/checkpoint_latest \
      --data_path frames_dir/ --output_dir out/ --stream --keyframe_every 4
"""

import argparse
import json
import os


def main():
    parser = argparse.ArgumentParser(description="Inference")
    parser.add_argument("--checkpoint_path", type=str, required=True)
    parser.add_argument("--data_path", type=str, required=True)
    parser.add_argument("--output_dir", type=str, required=True)
    parser.add_argument("--gpus", type=str, default=None,
                        help="ignored (reference-CLI parity)")
    parser.add_argument("--extra_config", type=str, default="{}")
    parser.add_argument("--stream", action="store_true",
                        help="streaming-session mode: --data_path is a frame "
                             "directory or video file; encode only keyframes")
    parser.add_argument("--keyframe_every", type=int, default=None,
                        help="stream keyframe cadence K (default: "
                             "serve.session.keyframe_every)")
    parser.add_argument("--drift_budget", type=float, default=None,
                        help="adaptive re-key threshold (default: "
                             "serve.session.drift_budget; 0 disables)")
    parser.add_argument("--drift_mode", type=str, default=None,
                        choices=("probe", "pose"),
                        help="drift proxy (default: serve.session.drift_mode)")
    args = parser.parse_args()

    from mine_tpu.utils import configure_compile_cache
    configure_compile_cache()

    import cv2
    import numpy as np
    import yaml

    from mine_tpu.config import CONFIG_DIR, load_config, postprocess
    from mine_tpu.infer.video import VideoGenerator
    from mine_tpu.train.step import SynthesisTrainer
    from mine_tpu.utils import make_logger

    os.makedirs(args.output_dir, exist_ok=True)
    logger = make_logger(os.path.join(args.output_dir, "inference.log"))

    ckpt_dir = os.path.dirname(os.path.abspath(args.checkpoint_path))
    params_yaml = os.path.join(ckpt_dir, "params.yaml")
    if os.path.exists(params_yaml):
        with open(params_yaml) as f:
            config = postprocess(yaml.safe_load(f))
        extra = json.loads(args.extra_config)
        config.update(extra)
    else:
        logger.info("No params.yaml next to checkpoint; using LLFF defaults")
        config = load_config(os.path.join(CONFIG_DIR, "params_llff.yaml"),
                             extra_config=args.extra_config)

    # build a state template, then load weights
    trainer = SynthesisTrainer(config, steps_per_epoch=1)
    state = trainer.init_state(batch_size=1)
    params, batch_stats = state.params, state.batch_stats

    if args.checkpoint_path.endswith(".npz"):
        from mine_tpu.train.checkpoint import load_pretrained_params
        params, batch_stats = load_pretrained_params(
            args.checkpoint_path, params, batch_stats, logger)
    else:
        from mine_tpu.train.checkpoint import CheckpointManager
        mgr = CheckpointManager(os.path.dirname(
            os.path.abspath(args.checkpoint_path)) or ".")
        restored = mgr.restore(state, os.path.abspath(args.checkpoint_path))
        if restored is None:
            raise FileNotFoundError(args.checkpoint_path)
        params, batch_stats = restored.params, restored.batch_stats
        logger.info("Restored checkpoint at step %d", int(restored.step))

    name = os.path.basename(os.path.normpath(args.data_path)).rsplit(".", 1)[0]
    if args.stream:
        from mine_tpu.config import serve_config_from_dict
        from mine_tpu.infer.video import (StreamRenderer, _colormap_frames,
                                          _to_uint8_frames, _write_video)
        from mine_tpu.utils import disparity_normalization_vis

        frames = _load_stream_frames(args.data_path)
        logger.info("Streaming %d frames from %s", len(frames),
                    args.data_path)
        serve_cfg = serve_config_from_dict(config)
        sr = StreamRenderer(
            config, params, batch_stats,
            keyframe_every=(args.keyframe_every
                            if args.keyframe_every is not None
                            else serve_cfg.session_keyframe_every),
            drift_budget=(args.drift_budget
                          if args.drift_budget is not None
                          else serve_cfg.session_drift_budget),
            drift_mode=(args.drift_mode if args.drift_mode is not None
                        else serve_cfg.session_drift_mode),
            probe_stride=serve_cfg.session_probe_stride,
            cache_quant=serve_cfg.cache_quant)
        try:
            rgb, disp = sr.stream(frames)
        finally:
            sr.close()
        stats = sr.last_stats or {}
        logger.info(
            "Session: frames=%d keyframes=%d rekeys=%d failed=%d",
            stats.get("frames", 0), stats.get("keyframes", 0),
            stats.get("rekeys", 0), stats.get("failed_frames", 0))
        disp_vis = disparity_normalization_vis(disp)
        written = [
            _write_video(_to_uint8_frames(rgb),
                         os.path.join(args.output_dir,
                                      f"{name}_stream_rgb"), 10),
            _write_video(_colormap_frames(disp_vis),
                         os.path.join(args.output_dir,
                                      f"{name}_stream_disp"), 10)]
    else:
        img = cv2.imread(args.data_path, cv2.IMREAD_COLOR)
        if img is None:
            raise FileNotFoundError(args.data_path)
        img = cv2.cvtColor(img, cv2.COLOR_BGR2RGB)

        gen = VideoGenerator(config, params, batch_stats, img)
        written = gen.render_videos(args.output_dir, name)
    for w in written:
        logger.info("wrote %s", w)


def _load_stream_frames(data_path):
    """Frames for --stream: a directory of images (sorted by filename) or a
    single video file (imageio/ffmpeg). RGB uint8/float arrays out."""
    import cv2
    import numpy as np

    if os.path.isdir(data_path):
        exts = (".png", ".jpg", ".jpeg", ".bmp")
        names = sorted(n for n in os.listdir(data_path)
                       if n.lower().endswith(exts))
        if not names:
            raise FileNotFoundError(
                f"no image frames ({'/'.join(exts)}) in {data_path}")
        frames = []
        for n in names:
            img = cv2.imread(os.path.join(data_path, n), cv2.IMREAD_COLOR)
            if img is None:
                raise FileNotFoundError(os.path.join(data_path, n))
            frames.append(cv2.cvtColor(img, cv2.COLOR_BGR2RGB))
        return frames
    import imageio
    return [np.asarray(f) for f in imageio.mimread(data_path, memtest=False)]


if __name__ == "__main__":
    main()
