"""What the benchmark asks of the program beyond its drivers' own wiring."""

from __future__ import annotations


def seeded_state(trainer, batch_size: int, seed: int):
    """`SynthesisTrainer.init_state`, with the seed an ARGUMENT of the one
    jitted program instead of a constant inside it.

    `init_state(seed=...)` closes over the seed, so every new seed is a new
    program: 25 s of compile on the v5e (chip run, PR 26) in the set-up of
    every run, since each run of a check has another seed. The body is
    init_state's own (zeros image, 0.5 disparity, `create_train_state`,
    replicated over the trainer's mesh); the seed arrives as an int32.
    PERF.md lists the program-side repair for a later PR."""
    import jax
    import jax.numpy as jnp

    from mine_tpu.parallel import mesh as mesh_lib
    from mine_tpu.train.state import create_train_state

    height, width = trainer.cfg.img_h, trainer.cfg.img_w

    def init(seed_i32):
        img = jnp.zeros((batch_size, height, width, 3), jnp.float32)
        disp = jnp.full((batch_size, trainer.cfg.num_bins_total), 0.5,
                        jnp.float32)
        return create_train_state(trainer.model, trainer.config,
                                  trainer.steps_per_epoch, img, disp,
                                  seed=seed_i32)

    out = (mesh_lib.replicated(trainer.mesh)
           if trainer.mesh is not None else None)
    return jax.jit(init, out_shardings=out)(jnp.int32(seed))
