#!/usr/bin/env python
"""Standalone evaluation: PSNR / SSIM / LPIPS over a validation set.

The reference embeds evaluation in the training loop (synthesis_task.run_eval
:476-507, rank-0 only); this CLI runs the same protocol against any
checkpoint — the parity-checking harness for released-checkpoint comparisons
(convert a MINE release with tools/convert_torch_weights.py mine, then point
--checkpoint_path at the .npz).

  python eval_cli.py --checkpoint_path ws/v1/checkpoint_latest \
      --config_path mine_tpu/configs/params_llff.yaml \
      --extra_config '{"data.training_set_path": "/data/nerf_llff_data"}'

Prints one JSON line with the averaged metrics.
"""

import argparse
import json
import os


def main(argv=None):
    parser = argparse.ArgumentParser(description="Evaluation")
    parser.add_argument("--checkpoint_path", type=str, required=True)
    parser.add_argument("--config_path", type=str, default=None)
    parser.add_argument("--extra_config", type=str, default="{}")
    parser.add_argument("--profile_dir", type=str, default=None,
                        help="write a jax.profiler trace of the eval steps")
    args = parser.parse_args(argv)

    import jax

    from mine_tpu.utils import configure_compile_cache
    configure_compile_cache()

    import yaml

    from mine_tpu.config import CONFIG_DIR, load_config, postprocess
    from mine_tpu.data.llff import get_dataset
    from mine_tpu.losses import lpips as lpips_mod
    from mine_tpu.train.loop import TrainLoop
    from mine_tpu.train.step import SynthesisTrainer
    from mine_tpu.utils import make_logger

    logger = make_logger()

    ckpt_dir = os.path.dirname(os.path.abspath(args.checkpoint_path))
    params_yaml = os.path.join(ckpt_dir, "params.yaml")
    if args.config_path:
        config = load_config(args.config_path, extra_config=args.extra_config)
    elif os.path.exists(params_yaml):
        with open(params_yaml) as f:
            config = postprocess(yaml.safe_load(f))
        extra = json.loads(args.extra_config)
        for k in extra:  # same unknown-key rejection as load_config
            if k not in config:
                raise KeyError(f"Unknown extra config key: {k}")
        config.update(extra)
    else:
        config = load_config(os.path.join(CONFIG_DIR, "params_llff.yaml"),
                             extra_config=args.extra_config)

    lpips_params = lpips_mod.load_params(lpips_mod.default_weights_path())
    if lpips_params is None:
        logger.info("LPIPS weights not found; lpips metric omitted "
                    "(reported as NaN internally, never 0)")

    trainer = SynthesisTrainer(config, steps_per_epoch=1,
                               lpips_params=lpips_params)
    state = trainer.init_state(trainer.global_batch_size())

    if args.checkpoint_path.endswith(".npz"):
        from mine_tpu.train.checkpoint import load_pretrained_params
        params, stats = load_pretrained_params(
            args.checkpoint_path, state.params, state.batch_stats, logger)
        state = state.replace(params=params, batch_stats=stats)
    else:
        from mine_tpu.train.checkpoint import CheckpointManager
        mgr = CheckpointManager(ckpt_dir)
        restored = mgr.restore(state, os.path.abspath(args.checkpoint_path))
        if restored is None:
            raise FileNotFoundError(args.checkpoint_path)
        state = restored
        logger.info("Restored checkpoint at step %d", int(state.step))

    _, val_ds = get_dataset(config, logger)
    loop = TrainLoop(trainer, val_ds, val_ds, workspace="/tmp/eval_ws",
                     logger=logger, tb_writer=None)

    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    results = loop.run_eval(state)
    if args.profile_dir:
        jax.profiler.stop_trace()
        logger.info("profiler trace written to %s", args.profile_dir)

    # NaN-valued metrics (e.g. LPIPS without weights) are omitted from the
    # JSON rather than emitted as invalid-JSON NaN tokens or a fake 0.0
    import math
    out = {k: round(v, 6) for k, v in results.items() if not math.isnan(v)}
    skipped = sorted(k for k, v in results.items() if math.isnan(v))
    if skipped:
        out["missing_metrics"] = skipped
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
