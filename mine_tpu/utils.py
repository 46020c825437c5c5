"""Host-side utilities: meters, logging, visualization helpers.

Replaces the host-side pieces of the reference's utils.py (AverageMeter :120,
disparity_normalization_vis :6, logger wiring in train.py:116-131). The
reference's device-side utils (Embedder -> models/embedder.py, inverse ->
geometry.py closed forms, restore_model -> train/checkpoint.py) live with
their layers.
"""

from __future__ import annotations

import logging
import os
import sys
from typing import Dict, Optional

import numpy as np


class AverageMeter:
    """Running average of a scalar metric (reference utils.py:120-141)."""

    def __init__(self, name: str, fmt: str = ":f"):
        self.name = name
        self.fmt = fmt
        self.reset()

    def reset(self):
        self.val = 0.0
        self.avg = 0.0
        self.sum = 0.0
        self.count = 0

    def update(self, val: float, n: int = 1):
        self.val = val
        self.sum += val * n
        self.count += n
        self.avg = self.sum / self.count

    def __str__(self):
        fmtstr = "{name} {val" + self.fmt + "} ({avg" + self.fmt + "})"
        return fmtstr.format(**self.__dict__)


def disparity_normalization_vis(disparity: np.ndarray) -> np.ndarray:
    """Min-max normalize [B,1,H,W] disparity per image for visualization
    (reference utils.py:6-17)."""
    d = np.asarray(disparity)
    dmin = d.min(axis=(1, 2, 3), keepdims=True)
    dmax = d.max(axis=(1, 2, 3), keepdims=True)
    return np.clip((d - dmin) / (dmax - dmin + 1e-12), 0.0, 1.0)


# the one in-checkout compile cache (git-ignored): the CLIs, bench.py, the
# dry run and chip_smoke.py all land here, so a second run from the same
# checkout starts from the first run's executables
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def configure_compile_cache() -> str:
    """Enable JAX's persistent compile cache and return its directory.

    The first compile of the full train step costs minutes; the cache makes
    every later invocation start in seconds. Where the caller has set
    JAX_COMPILATION_CACHE_DIR, JAX reads it itself and nothing is set
    here; otherwise the cache lives at COMPILE_CACHE_DIR. The path is part
    of the cache key, so it is fixed: never a temporary name.
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache)
    return cache


def refuse_on_tpu(what: str) -> None:
    """For harnesses that start several JAX processes on one machine (HTTP
    between serve hosts): a TPU chip belongs to one process at a time, and
    the caller already holds it, so the children would hang or die. Such a
    harness checks behaviour and counts bytes, not device speed — on a TPU
    it says so and stops, rather than hang or quietly move its children to
    the CPU and print their rate as a device number."""
    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what} starts several JAX processes on this machine, and a "
            f"TPU chip belongs to one process at a time; it has no device "
            f"number to give. Run it with JAX_PLATFORMS=cpu.")


def describe_runtime() -> Dict[str, object]:
    """What the process runs on, as JAX reports it: the line every CLI logs
    at start-up and chip_smoke.py reads the device from."""
    import importlib.metadata

    import jax
    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def make_logger(log_file: Optional[str] = None,
                name: str = "mine_tpu") -> logging.Logger:
    """File + stdout logger (reference train.py:116-131)."""
    logger = logging.getLogger(name)
    formatter = logging.Formatter("[%(asctime)s %(filename)s] %(message)s")
    handlers = [logging.StreamHandler(sys.stdout)]
    if log_file:
        handlers.append(logging.FileHandler(log_file))
    for h in handlers:
        h.setFormatter(formatter)
    logger.handlers = handlers
    logger.setLevel(logging.INFO)
    logger.propagate = False
    return logger


def metrics_to_float(metrics: Dict) -> Dict[str, float]:
    """Step metrics as floats; a vector metric `k` of n entries becomes
    `k.1` .. `k.n` (the looped model's per-pass terms)."""
    out = {}
    for k, v in metrics.items():
        if np.ndim(v) == 0:
            out[k] = float(v)
        else:
            for i, x in enumerate(np.asarray(v).ravel(), 1):
                out["%s.%d" % (k, i)] = float(x)
    return out
