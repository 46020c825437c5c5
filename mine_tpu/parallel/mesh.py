"""Device mesh + sharding helpers — the runtime/comm layer.

Replaces the reference's torch.distributed/NCCL stack (train.py:63, DDP at
synthesis_task.py:108,112, SyncBatchNorm at :106-111, DistributedSampler at
train.py:83) with single-controller JAX SPMD:

  * mesh axes: ("data", "plane") — "data" is classic data parallelism (the
    gradient psum the reference got from DDP all-reduce), "plane" shards the
    S MPI-plane axis. The decoder's effective batch is B*S
    (depth_decoder.py:105-116), so sharding planes is this workload's
    sequence-parallel analog (SURVEY.md section 5, long-context row): the
    heavy conv stack parallelizes over data*plane, and the cross-plane
    compositing scan (cumprod over S) is handled by GSPMD with collectives
    along "plane".
  * gradients/BN statistics: plain array math under jit over the mesh; XLA
    inserts the all-reduces (no hand-written collectives needed).
  * multi-host: call `jax.distributed.initialize()` before building the mesh;
    the same code then runs over ICI+DCN.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
PLANE_AXIS = "plane"


def num_slices(devices: Sequence) -> int:
    """Distinct TPU slices among `devices` (1 when the attribute is absent,
    e.g. CPU/virtual devices). Multi-slice deployments connect slices over
    DCN, which is orders of magnitude slower than intra-slice ICI."""
    return len({getattr(d, "slice_index", 0) for d in devices})


def make_mesh(data: int = -1, plane: int = 1,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a ("data", "plane") mesh.

    data=-1 uses all remaining devices on the data axis. "plane" sits on the
    innermost (fastest ICI) axis: the plane collectives (compositing scan,
    decoder resharding) are latency-bound.

    Multi-slice topology awareness: when the devices span >1 TPU slice, the
    "data" axis is laid out so that SLICES differ only along it — the once-
    per-step gradient all-reduce is the only collective that crosses DCN,
    and every "plane" collective stays on intra-slice ICI. (jax
    mesh_utils.create_hybrid_device_mesh; requires plane parallelism to fit
    within one slice, which it must for latency anyway.)
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if data == -1:
        assert n % plane == 0, (n, plane)
        data = n // plane
    assert data * plane == n, f"{data}x{plane} != {n} devices"

    ns = num_slices(devices)
    if ns > 1:
        assert data % ns == 0, (
            f"data axis ({data}) must be divisible by the slice count "
            f"({ns}): the plane axis cannot straddle DCN")
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_hybrid_device_mesh(
            (data // ns, plane), (ns, 1), devices=devices)
    else:
        dev_array = np.asarray(devices).reshape(data, plane)
    return Mesh(dev_array, (DATA_AXIS, PLANE_AXIS))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Per-example arrays: shard the leading batch dim over "data"."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def put_batch(np_batch, mesh: Optional[Mesh]):
    """Host batch dict -> device arrays under the mesh's INPUT sharding.

    The input-staging primitive (SynthesisTrainer.put_batch and the
    DeviceStager both land here): per-example arrays are committed with
    the batch dim sharded over "data", so the jitted step's in_shardings
    match without a device-side reshard. Without a mesh, a plain
    device_put (uncommitted default-device placement, like jnp.asarray).
    Multi-host, each process contributes its local shard
    (jax.make_array_from_process_local_data).

    `jax.device_put` only ENQUEUES the copy — callers that want the copy
    off the critical path (the stager's double buffer) block on the
    result in a background thread, not here.
    """
    import jax.numpy as jnp
    if mesh is None:
        return {k: jnp.asarray(v) for k, v in np_batch.items()}
    sharding = batch_sharding(mesh)
    if jax.process_count() == 1:
        return {k: jax.device_put(v, sharding) for k, v in np_batch.items()}
    return {k: jax.make_array_from_process_local_data(sharding, v)
            for k, v in np_batch.items()}


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """`jax.shard_map` with the variance check off, for every in-repo call
    site: the wrapped bodies contain pallas_call outputs, which carry no
    mesh-variance info for the checker to verify."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def constrain(x, mesh: Optional[Mesh], *spec):
    """with_sharding_constraint that degrades to a no-op without a mesh.

    Keeps the loss graph annotatable while the same code runs single-device
    (tests, single-chip bench).
    """
    if mesh is None or mesh.size == 1:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
