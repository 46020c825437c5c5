"""Serving mesh: named-axis device mesh + sharding specs for the fleet.

The training mesh (mine_tpu/parallel/mesh.py) spans ("data", "plane") for
the encoder's gradient work; serving has a different parallel structure —
one jitted render-only program whose batch axis is POSES, not images — so
the fleet gets its own mesh with serving-native axis names:

  * "batch": the pose/request axis. Every op in the render program is
    per-pose independent (engine.py docstring), so sharding P along
    "batch" is embarrassingly parallel: each device renders its pose rows
    with the identical per-row program, which is why the mesh render stays
    BITWISE-identical to the single-device engine (tests/test_serve_fleet).
  * "model": the S plane axis of the cached MPI stack, for plane counts too
    large for one device's HBM. Cross-plane compositing (cumprod over S)
    makes GSPMD insert collectives along this axis — the same structure the
    training mesh's "plane" axis has.

`MeshRenderEngine` is the PR-5 `RenderEngine` with its ONE jitted program
given `NamedSharding` in/out specs: inputs are committed under the specs
before dispatch (the `_place` hook), outputs land pose-sharded. The pow2
bucket discipline is preserved — pose buckets are floored at the "batch"
axis size so every bucket divides evenly across the mesh, and the compile
set stays bounded at log2(max_bucket) x log2(max_requests) per mesh shape.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mine_tpu.serve.engine import RenderEngine, pow2_bucket

SERVE_BATCH_AXIS = "batch"
SERVE_MODEL_AXIS = "model"


def _check_pow2(name: str, n: int) -> None:
    if n < 1 or (n & (n - 1)) != 0:
        raise ValueError(
            f"{name} must be a power of two >= 1, got {n} (pow2 mesh axes "
            f"compose with the engine's pow2 shape buckets: every bucket "
            f"divides evenly across the mesh)")


def make_serve_mesh(batch: int = 1, model: int = 1,
                    devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a ("batch", "model") serving mesh over the first batch*model
    devices. Both axis sizes must be powers of two (see _check_pow2)."""
    _check_pow2("serve.mesh_batch", batch)
    _check_pow2("serve.mesh_model", model)
    if devices is None:
        devices = jax.devices()
    n = batch * model
    if n > len(devices):
        raise ValueError(
            f"serve mesh {batch}x{model} needs {n} devices, "
            f"have {len(devices)}")
    dev_array = np.asarray(devices[:n]).reshape(batch, model)
    return Mesh(dev_array, (SERVE_BATCH_AXIS, SERVE_MODEL_AXIS))


def render_shardings(mesh: Mesh) -> dict:
    """NamedShardings for the render program's operands/results, keyed by
    operand name (the _render_impl signature):

      planes [R,S,4,H,W], scales [R,S,4,1,1], disp [R,S]: S along "model"
      K / K_inv [R,3,3]: replicated (tiny)
      idx [P], G [P,4,4], rgb/depth out [P,...]: P along "batch"
    """
    model = P(None, SERVE_MODEL_AXIS) \
        if mesh.shape[SERVE_MODEL_AXIS] > 1 else P()
    return {
        "planes": NamedSharding(mesh, model),
        "scales": NamedSharding(mesh, model),
        "disp": NamedSharding(mesh, model),
        "K": NamedSharding(mesh, P()),
        "K_inv": NamedSharding(mesh, P()),
        "idx": NamedSharding(mesh, P(SERVE_BATCH_AXIS)),
        "G": NamedSharding(mesh, P(SERVE_BATCH_AXIS)),
        "out": NamedSharding(mesh, P(SERVE_BATCH_AXIS)),
    }


class MeshRenderEngine(RenderEngine):
    """RenderEngine whose one jitted program spans a serving mesh.

    Same cache facade, same bucketed dispatch, same render math — the only
    deltas are (1) pose buckets floor at the "batch" axis size so the pose
    dim always divides across the mesh, (2) operands are device_put under
    the `render_shardings` specs before the call (`_place`), and (3) the
    jit carries pose-sharded out_shardings. Parity with the single-device
    engine is bitwise on 1/2/4-device CPU meshes (tests/test_serve_fleet);
    8 devices inherits the known GSPMD CPU divergence (ROADMAP).
    """

    def __init__(self, mesh_batch: int = 1, mesh_model: int = 1,
                 devices: Optional[Sequence[jax.Device]] = None, **kw):
        super().__init__(**kw)
        self.mesh = make_serve_mesh(mesh_batch, mesh_model, devices)
        self.mesh_batch = mesh_batch
        self.mesh_model = mesh_model
        self._shardings = render_shardings(self.mesh)
        # pose counts pad to pow2 buckets >= the batch axis, so every
        # bucket splits evenly (pow2 / pow2) with no ragged shard
        self._min_pose_bucket = mesh_batch
        out = self._shardings["out"]
        self._render = jax.jit(self._render_impl,
                               static_argnames=("warp_impl",),
                               out_shardings=(out, out))

    def num_devices(self) -> int:
        return self.mesh.size

    def _mesh_desc(self) -> str:
        """AOT program-key component (engine._program_key): executables are
        compiled against committed NamedSharding inputs, so a 2x1 artifact
        must never be handed to a 1x1 engine (or vice versa)."""
        return f"{self.mesh_batch}x{self.mesh_model}"

    def _render_span_fields(self) -> dict:
        """Request traces rendered here carry the mesh topology, so a
        waterfall read offline still knows which fleet shape it measured."""
        return {"mesh": f"{self.mesh_batch}x{self.mesh_model}",
                "devices": self.mesh.size}

    def _place(self, planes, scales, disp, K, K_inv, idx, poses):
        """Commit every operand under its NamedSharding; the committed
        inputs are what make the jitted program span the mesh."""
        if self.mesh_model > 1 and planes.shape[1] % self.mesh_model:
            raise ValueError(
                f"plane count S={planes.shape[1]} must divide the model "
                f"axis ({self.mesh_model})")
        s = self._shardings
        put = jax.device_put
        return (put(planes, s["planes"]),
                None if scales is None else put(scales, s["scales"]),
                put(disp, s["disp"]),
                put(K, s["K"]),
                put(K_inv, s["K_inv"]),
                put(idx, s["idx"]),
                put(poses, s["G"]))
