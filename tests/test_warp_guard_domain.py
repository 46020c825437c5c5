"""Guard-domain property tests of the guarded warp backend across meshes.

The `warp_fallback_frac` training metric is only trustworthy if the
with_domain_flag plumbing reports pallas_diff's ACTUAL lax.cond decision —
not a lookalike recomputation. Property: on every mesh shape (single
device, 2- and 4-device data meshes), the flag equals EXACTLY the
fraction of shards whose own guard_ok passes — 1.0 on randomized
translation-dominated poses, 0.0 on an adversarial rotation-heavy one,
with the expectation derived by replaying the homography math and calling
the exported guard_ok directly (ops/warp.py builds the flag from that same
function, so a drift between cond and flag is what this catches). The
`warp_subband_frac` diagnostic is sharded the same way and held the same
way, against kernels.warp.subband_frac per shard.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mine_tpu import geometry
from mine_tpu.kernels import warp as kernels_warp
from mine_tpu.kernels import warp_vjp
from mine_tpu.ops.warp import homography_warp
from mine_tpu.parallel import mesh as mesh_lib

B, C, H, W = 8, 3, 32, 32

# the guarded backend, its band (24: the kernels' domain budgets the
# SUBLANE_ALIGN-1 slack) and its exported guard_ok(src_shape, coords_y)
IMPL, BAND = "pallas_diff", 24
GUARD = functools.partial(warp_vjp.guard_ok, band=BAND)


def _setup(seed=7):
    src = jax.random.uniform(jax.random.PRNGKey(seed), (B, C, H, W))
    d = jnp.linspace(1.0, 4.0, B)
    K = jnp.asarray(geometry.intrinsics_from_fov(H, W, 60.0))[None].repeat(B, 0)
    K_inv = geometry.inverse_intrinsics(K)
    grid = geometry.cached_pixel_grid(H, W)
    return src, d, K, K_inv, grid


def _translation_pose(seed):
    """Translation-dominated pose: small random t, no rotation."""
    rng = np.random.RandomState(seed)
    G = jnp.eye(4)[None].repeat(B, 0)
    t = rng.uniform(-0.05, 0.05, size=(B, 3)).astype(np.float32)
    return G.at[:, 0:3, 3].set(jnp.asarray(t))


def _adversarial_pose():
    """Strong in-plane rotation: source rows sweep the image, every
    row-block's span blows any practical band on every shard."""
    a = 0.6
    R = jnp.asarray([[np.cos(a), -np.sin(a), 0.0, 0.0],
                     [np.sin(a), np.cos(a), 0.0, 0.0],
                     [0.0, 0.0, 1.0, 0.0],
                     [0.0, 0.0, 0.0, 1.0]], jnp.float32)
    return jnp.broadcast_to(R, (B, 4, 4))


def _source_coords(d, G, K_inv, K, grid):
    """Replay homography_warp's coordinate derivation (ops/warp.py): the
    exact source (x, y) fields the backend sees."""
    H_tgt_src = geometry.homography_tgt_src(K, K_inv, G, d)
    H_src_tgt = geometry.inverse_3x3(H_tgt_src)
    g = grid.reshape(3, H * W)
    src_homo = jnp.einsum("bij,jn->bin", H_src_tgt, g)
    src_xy = src_homo[:, 0:2, :] / src_homo[:, 2:3, :]
    return (src_xy[:, 0, :].reshape(B, H, W),
            src_xy[:, 1, :].reshape(B, H, W))


def _expected_flag(guard, cy, mesh):
    """The flag contract: on a multi-device mesh the cond decides PER SHARD
    and the guards are pmean'd; otherwise it decides globally."""
    n = 1 if mesh is None else mesh.size
    shards = np.split(np.asarray(cy), n, axis=0)
    per = [float(guard((B // n, C, H, W), jnp.asarray(s))) for s in shards]
    return float(np.mean(per))


def _mesh(n):
    if n is None:
        return None
    return mesh_lib.make_mesh(data=n, plane=1, devices=jax.devices()[:n])


@pytest.mark.parametrize("mesh_n", [None, 2, 4])
def test_flag_matches_guard(mesh_n):
    src, d, K, K_inv, grid = _setup()
    mesh = _mesh(mesh_n)
    # seed sweep only single-device: the mesh cases re-check the SAME guard
    # math per shard, so one in-band pose + the adversarial one suffice
    # (interpret-mode Pallas on CPU makes each mesh eval expensive)
    seeds = (0, 1, 2) if mesh_n is None else (0,)
    poses = [("trans%d" % s, _translation_pose(s), 1.0) for s in seeds]
    poses.append(("rot", _adversarial_pose(), 0.0))
    for name, G, want in poses:
        _, cy = _source_coords(d, G, K_inv, K, grid)
        expected = _expected_flag(GUARD, cy, mesh)
        # the constructed poses are unambiguous: fully in-band or fully out
        assert expected == want, (mesh_n, name, expected)
        _, _, flag = homography_warp(src, d, G, K_inv, K, grid, impl=IMPL,
                                     band=BAND, mesh=mesh,
                                     with_domain_flag=True)
        assert float(flag) == expected, (mesh_n, name, float(flag))


def test_flag_partial_fallback_on_mixed_shards():
    """A mesh where ONE of two shards draws an out-of-band pose must report
    the fraction (0.5), not collapse to all-or-nothing."""
    src, d, K, K_inv, grid = _setup()
    mesh = _mesh(2)
    G = _translation_pose(0)
    # second half of the batch (shard 1 under P(("data","plane"))): rotation
    G = G.at[B // 2:].set(_adversarial_pose()[B // 2:])
    _, cy = _source_coords(d, G, K_inv, K, grid)
    assert _expected_flag(GUARD, cy, mesh) == 0.5
    _, _, flag = homography_warp(src, d, G, K_inv, K, grid, impl=IMPL,
                                 band=BAND, mesh=mesh, with_domain_flag=True)
    assert float(flag) == 0.5, float(flag)


def test_flag_nan_for_unguarded_backend():
    """Plain xla has no runtime guard: the flag must be NaN, never a fake
    0.0/1.0 that would pollute the warp_fallback_frac metric."""
    src, d, K, K_inv, grid = _setup()
    _, _, flag = homography_warp(src, d, _translation_pose(0), K_inv, K, grid,
                                 impl="xla", with_domain_flag=True)
    assert np.isnan(float(flag))


@pytest.mark.parametrize("mesh_n", [None, 2, 4])
def test_subband_frac_is_mean_over_shards(mesh_n):
    """with_subband_frac under shard_map: each shard counts its OWN windows
    (kernels.warp.subband_frac on its planes) and the pmean is their mean:
    a share taken from one shard's coordinates would not match."""
    src, d, K, K_inv, grid = _setup()
    mesh = _mesh(mesh_n)
    band = H  # the whole image: every pose is in-band, rotation included
    # rotation about the optical axis growing along the batch: a row's
    # taps slope across the width, more units overflow their 16-row
    # sub-band on the later planes, and the shards' shares differ
    a = np.linspace(0.2, 0.4, B)
    rot = np.stack([np.stack([np.cos(a), -np.sin(a)], -1),
                    np.stack([np.sin(a), np.cos(a)], -1)], -2)
    G = _translation_pose(3).at[:, 0:2, 0:2].set(
        jnp.asarray(rot, jnp.float32))
    cx, cy = _source_coords(d, G, K_inv, K, grid)
    n = 1 if mesh is None else mesh.size
    assert _expected_flag(functools.partial(warp_vjp.guard_ok, band=band),
                          cy, mesh) == 1.0
    per = [float(kernels_warp.subband_frac((B // n, C, H, W),
                                           jnp.asarray(sx), jnp.asarray(sy),
                                           band))
           for sx, sy in zip(np.split(np.asarray(cx), n),
                             np.split(np.asarray(cy), n))]
    *_, flag, frac = homography_warp(
        src, d, G, K_inv, K, grid, impl=IMPL, band=band, mesh=mesh,
        with_domain_flag=True, with_subband_frac=True)
    assert float(flag) == 1.0
    np.testing.assert_allclose(float(frac), np.mean(per), rtol=0, atol=1e-6)
    assert 0.0 < float(frac) < 1.0 and len(set(per)) == len(per), per


def test_indivisible_flat_batch_takes_the_gather():
    """A flat batch the mesh does not divide (a remainder eval example)
    cannot run the kernel under shard_map: the values are the gather's,
    bitwise, and both diagnostics say so (0.0, not NaN and not 1.0)."""
    src, d, K, K_inv, grid = _setup()
    G = _translation_pose(0)
    src, d, G, K, K_inv = (a[:B - 1] for a in (src, d, G, K, K_inv))
    out, valid, flag, frac = homography_warp(
        src, d, G, K_inv, K, grid, impl=IMPL, band=BAND, mesh=_mesh(2),
        with_domain_flag=True, with_subband_frac=True)
    ref, ref_valid = homography_warp(src, d, G, K_inv, K, grid, impl="xla")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(ref_valid))
    assert float(flag) == 0.0 and float(frac) == 0.0
