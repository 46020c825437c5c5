"""Shared switch for the Pallas kernel equivalence suites.

On CPU (the default suite) kernels run in interpret mode; with
JAX_PLATFORMS=tpu the SAME tests compile the real kernels on the chip.
Keeping the flag here (not hardcoded interpret=True in each test) is what
makes that pass actually compile something.

A function, not a constant: jax.default_backend() initializes (and
freezes) the backend, which must not happen as a side effect of merely
importing this module.
"""


def interpret() -> bool:
    from mine_tpu.kernels import on_tpu_backend
    return not on_tpu_backend()
