#!/usr/bin/env python
"""Training entry point — CLI-compatible with the reference's train.py.

  python train_cli.py --config_path mine_tpu/configs/params_llff.yaml \
      --workspace /path/ws --version v1 \
      --extra_config '{"training.epochs": 100}'

Differences from the reference launcher (reference: train.py +
start_training.sh): single-controller JAX replaces torch.distributed.launch —
no --local_rank, no CUDA_VISIBLE_DEVICES juggling, no NCCL rendezvous. On a
multi-host TPU pod, set the standard JAX coordination env vars and pass
--distributed to call jax.distributed.initialize(); the mesh then spans all
hosts and the loop shards data by process index.
"""

import argparse
import json
import os
import shutil
import sys


def main():
    parser = argparse.ArgumentParser(description="Training")
    parser.add_argument("--config_path", default=None, type=str)
    parser.add_argument("--workspace", type=str, required=True)
    parser.add_argument("--version", type=str, required=True)
    parser.add_argument("--extra_config", type=str, default="{}")
    parser.add_argument("--distributed", action="store_true",
                        help="call jax.distributed.initialize() (multi-host)")
    parser.add_argument("--plane_parallel", type=int, default=None,
                        help="override parallel.plane_parallel")
    args = parser.parse_args()

    import jax

    from mine_tpu.utils import configure_compile_cache
    compile_cache = configure_compile_cache()

    if args.distributed:
        jax.distributed.initialize()

    from mine_tpu.config import CONFIG_DIR, load_config, save_config
    from mine_tpu.data.llff import get_dataset
    from mine_tpu.parallel.mesh import make_mesh
    from mine_tpu.train.loop import TrainLoop
    from mine_tpu.train.trainer import make_trainer
    from mine_tpu.utils import describe_runtime, make_logger

    config_path = args.config_path or os.path.join(CONFIG_DIR,
                                                   "params_llff.yaml")
    config = load_config(config_path, extra_config=args.extra_config)
    if args.plane_parallel is not None:
        config["parallel.plane_parallel"] = args.plane_parallel

    # chaos-test seams (testing.fault_plan / MINE_TPU_FAULTS env JSON);
    # no-op in production. Must run before the trainer is constructed —
    # the NaN-grad injection is resolved at trace time.
    from mine_tpu.testing import faults
    fault_plan = faults.activate(config)

    workspace = os.path.join(args.workspace, args.version)
    is_lead = jax.process_index() == 0
    if is_lead:
        os.makedirs(workspace, exist_ok=True)
        save_config(config, os.path.join(workspace, "params.yaml"))

    log_file = os.path.join(workspace, "training.log") if is_lead else None
    logger = make_logger(log_file)
    logger.info("Training config: %s", json.dumps(
        {k: v for k, v in config.items() if isinstance(v, (str, int, float,
                                                           bool, list))},
        indent=0))
    logger.info("JAX devices: %s (process %d/%d)", jax.devices(),
                jax.process_index(), jax.process_count())
    logger.info("Runtime: %s", json.dumps(
        dict(describe_runtime(), compile_cache=compile_cache)))

    tb_writer = None
    if is_lead:
        try:
            from tensorboardX import SummaryWriter
            tb_writer = SummaryWriter(log_dir=workspace)
        except ImportError:
            logger.warning("tensorboardX unavailable; scalar logging only")

    # mesh: data x plane over all devices
    plane = int(config.get("parallel.plane_parallel", 1))
    data = int(config.get("parallel.data_parallel", -1))
    n_dev = len(jax.devices())
    mesh = None
    if n_dev > 1 or plane > 1:
        mesh = make_mesh(data=data, plane=plane)
        logger.info("Mesh: %s", mesh)

    train_ds, val_ds = get_dataset(config, logger)

    # model.family selects the trainer (and, through data.name, the dataset
    # above); each family's modules are imported only on its own path
    family_kwargs = {}
    if config.get("model.family", "mine") == "mine":
        from mine_tpu.losses import lpips as lpips_mod
        lpips_params = lpips_mod.load_params(lpips_mod.default_weights_path())
        if lpips_params is None:
            logger.info("LPIPS weights not found (%s); lpips metric disabled",
                        lpips_mod.default_weights_path())
        family_kwargs["lpips_params"] = lpips_params

    # steps_per_epoch drives the LR schedule AND the loop's epoch accounting —
    # computed once from the global batch geometry (per-device batch x data
    # axis size), then owned by the trainer
    from mine_tpu.parallel.mesh import DATA_AXIS
    data_size = mesh.shape[DATA_AXIS] if mesh is not None else 1
    global_batch = int(config["data.per_gpu_batch_size"]) * data_size
    steps_per_epoch = max(1, len(train_ds) // global_batch)
    trainer = make_trainer(config, mesh=mesh, steps_per_epoch=steps_per_epoch,
                           **family_kwargs)
    logger.info("Trainer: %s", type(trainer).__name__)
    if hasattr(trainer.cfg, "warp_backend"):
        logger.info("Backends: warp=%s composite=%s",
                    trainer.cfg.warp_backend, trainer.cfg.composite_backend)

    state = trainer.init_state(trainer.global_batch_size())
    pretrained = config.get("model.pretrained_weights_path") or \
        config.get("training.pretrained_checkpoint_path")
    if pretrained and str(pretrained).endswith(".npz"):
        from mine_tpu.train.checkpoint import load_pretrained_params
        new_params, new_stats = load_pretrained_params(
            pretrained, state.params, state.batch_stats, logger)
        state = state.replace(params=new_params, batch_stats=new_stats)
        logger.info("Loaded pretrained weights from %s", pretrained)

    if fault_plan is not None:
        logger.warning("FAULT INJECTION ACTIVE: %s", fault_plan)

    loop = TrainLoop(trainer, train_ds, val_ds, workspace,
                     logger=logger, tb_writer=tb_writer)
    state = loop.run(state)
    # where the run left things: one parameter leaf's placement, and what
    # each local device holds now and held at its peak (None on the CPU)
    leaf = jax.tree_util.tree_leaves(state.params)[0]
    logger.info("Param placement: %s", json.dumps({
        "devices": len(leaf.sharding.device_set),
        "replicated": bool(leaf.sharding.is_fully_replicated)}))
    logger.info("Device memory: %s", json.dumps([
        dict(id=d.id, **{k: (d.memory_stats() or {}).get(k)
                         for k in ("bytes_in_use", "peak_bytes_in_use")})
        for d in jax.local_devices()]))
    if loop.preempted:
        # clean preemption exit: the emergency checkpoint is on disk and a
        # relaunch resumes exactly; exit 0 so supervisors treat this as a
        # graceful drain, not a crash loop
        logger.info("Exiting after preemption checkpoint — relaunch to "
                    "resume")
        sys.exit(0)


if __name__ == "__main__":
    main()
