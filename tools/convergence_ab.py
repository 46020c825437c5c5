#!/usr/bin/env python
"""Chip-free convergence A/Bs for the two asserted-but-unmeasured training
knobs (round-3 VERDICT item 2):

  (a) plane-chunked decoding (training.decoder_plane_chunks > 1) switches
      decoder BN to per-chunk "ghost" batch statistics (models/mpi.py:13-23)
      — eval-mode invariance is test-gated, but TRAINING dynamics were only
      asserted benign;
  (b) training.dtype bfloat16 is the bench default, while the only
      training-dynamics evidence ran f32 (CPU conv support).

Protocol: the round-3 synthetic-overfit recipe (train_cli's stack driven
directly: one scene, fixed seeds, N-step loss/PSNR curves), run as matched
pairs that differ in exactly one knob. Same seeds -> same disparity samples
and data order, so curve divergence isolates the knob.

  python tools/convergence_ab.py --steps 400 --out ab_results.json
  python tools/convergence_ab.py --pairs chunk --steps 200   # one pair only

Emits one JSON blob with per-run loss/PSNR curves + summary deltas, and a
human-readable verdict per pair (final-window means and a stated
tolerance). CPU-runnable: bf16 matmuls/convs work on CPU (slower, emulated
where needed); the dtype pair exercises the REAL training.dtype code path.
"""

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def run_one(name, overrides, steps, log_every=20):
    """Fixed-seed synthetic training run; returns loss/psnr curves."""
    import jax
    import jax.numpy as jnp

    from mine_tpu.config import CONFIG_DIR, load_config
    from mine_tpu.data.llff import get_dataset
    from mine_tpu.train.step import SynthesisTrainer

    config = load_config(os.path.join(CONFIG_DIR, "params_default.yaml"))
    config.update({
        "data.name": "synthetic",
        "data.img_h": 64, "data.img_w": 96,
        "data.per_gpu_batch_size": 2,
        "data.num_seq_per_gpu": 1,
        "data.visible_point_count": 32,
        "mpi.num_bins_coarse": 8,
        "mpi.disparity_start": 1.0, "mpi.disparity_end": 0.1,
        "model.num_layers": 18,
        "training.dtype": "float32",
    })
    config.update(overrides)

    train_ds, _ = get_dataset(config, logger=None)
    trainer = SynthesisTrainer(config, steps_per_epoch=10 ** 6)
    state = trainer.init_state(batch_size=2)

    losses, psnrs = [], []
    step, epoch = 0, 0
    while step < steps:
        for batch_np in train_ds.batch_iterator(
                batch_size=2, shuffle=True, seed=0, epoch=epoch,
                drop_last=True, shard_index=0, num_shards=1):
            if step >= steps:
                break
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            state, metrics = trainer.train_step(state, batch)
            if step % log_every == 0 or step == steps - 1:
                jax.block_until_ready(metrics)
                losses.append([step, float(metrics["loss"])])
                psnrs.append([step, float(metrics["psnr_tgt"])])
                print(f"  [{name}] step {step}: loss={losses[-1][1]:.4f} "
                      f"psnr={psnrs[-1][1]:.2f}", flush=True)
            step += 1
        epoch += 1
    return {"loss_curve": losses, "psnr_curve": psnrs,
            "final_loss": float(np.mean([v for _, v in losses[-3:]]))}


PAIRS = {
    # (a) ghost-BN: chunked vs unchunked, identical seeds. Tolerance: the
    # chunked run must reach a final-window loss within 15% relative — the
    # ghost-BN literature direction is "same or slightly better
    # generalization, slightly noisier optimization".
    "chunk": ({"training.decoder_plane_chunks": 1},
              {"training.decoder_plane_chunks": 4}, 0.15),
    # (b) storage/compute dtype: f32 vs bf16 through the REAL
    # training.dtype path. Tolerance 15% relative on the final window.
    "dtype": ({"training.dtype": "float32"},
              {"training.dtype": "bfloat16"}, 0.15),
}


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=400)
    parser.add_argument("--pairs", default="chunk,dtype")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    results, ok_all = {}, True
    for pair in args.pairs.split(","):
        a_cfg, b_cfg, tol = PAIRS[pair]
        print(f"== pair '{pair}': A={a_cfg} B={b_cfg}", flush=True)
        a = run_one(f"{pair}:A", a_cfg, args.steps)
        b = run_one(f"{pair}:B", b_cfg, args.steps)
        rel = abs(b["final_loss"] - a["final_loss"]) / max(
            abs(a["final_loss"]), 1e-9)
        ok = bool(rel <= tol)
        ok_all &= ok
        results[pair] = {"A": a, "B": b, "rel_final_delta": rel,
                         "tolerance": tol, "within_tolerance": ok}
        print(f"== pair '{pair}': final A={a['final_loss']:.4f} "
              f"B={b['final_loss']:.4f} rel_delta={rel:.3f} "
              f"(tol {tol}) -> {'OK' if ok else 'DIVERGED'}", flush=True)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
    print(json.dumps({p: {"rel_final_delta": r["rel_final_delta"],
                          "within_tolerance": r["within_tolerance"]}
                      for p, r in results.items()}))
    return 0 if ok_all else 1


if __name__ == "__main__":
    sys.exit(main())
