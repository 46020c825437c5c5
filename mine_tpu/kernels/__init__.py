from mine_tpu.kernels.composite import (fused_src_render_blend,  # noqa: F401
                                        fused_volume_render)


def on_tpu_backend() -> bool:
    """True when the default JAX backend is a TPU, where Pallas kernels
    compile natively; elsewhere they run in interpret mode."""
    import jax

    return jax.default_backend() == "tpu"
