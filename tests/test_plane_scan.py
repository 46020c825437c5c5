"""Distributed plane-axis composite (ops/plane_scan.py) vs the serial
renderer: values AND gradients must match on the 8-device mesh — the
two-level transparency scan (local cumprod + shard-total prefix combine +
halo exchange) is exact, not approximate."""

import jax
import jax.numpy as jnp
import numpy as np

from mine_tpu.ops import rendering
from mine_tpu.ops.plane_scan import plane_sharded_volume_render
from mine_tpu.parallel import mesh as mesh_lib


def _volume(seed, B=2, S=8, H=16, W=24):
    rng = np.random.RandomState(seed)
    rgb = jnp.asarray(rng.uniform(size=(B, S, 3, H, W)).astype(np.float32))
    sigma = jnp.asarray(
        rng.uniform(0.0, 3.0, size=(B, S, 1, H, W)).astype(np.float32))
    # plane point clouds at increasing depth with some xy jitter; a few
    # negative-z points exercise the z-mask
    z = np.linspace(1.0, 5.0, S)[None, :, None, None, None]
    xyz = np.concatenate([
        rng.normal(size=(B, S, 2, H, W)) * 0.05,
        np.broadcast_to(z, (B, S, 1, H, W)) +
        rng.normal(size=(B, S, 1, H, W)) * 0.01,
    ], axis=2).astype(np.float32)
    xyz[:, :, 2][rng.uniform(size=(B, S, H, W)) < 0.05] *= -1.0
    return rgb, sigma, jnp.asarray(xyz)


def _serial(rgb, sigma, xyz, z_mask, is_bg):
    if z_mask:
        sigma = jnp.where(xyz[:, :, 2:3] >= 0.0, sigma, 0.0)
    out_rgb, out_depth, _, _ = rendering.plane_volume_rendering(
        rgb, sigma, xyz, is_bg_depth_inf=is_bg)
    return out_rgb, out_depth


def test_matches_serial_composite():
    mesh = mesh_lib.make_mesh(data=2, plane=4)
    rgb, sigma, xyz = _volume(0)
    for z_mask in (False, True):
        for is_bg in (False, True):
            got = plane_sharded_volume_render(
                rgb, sigma, xyz, mesh, z_mask=z_mask, is_bg_depth_inf=is_bg)
            want = _serial(rgb, sigma, xyz, z_mask, is_bg)
            np.testing.assert_allclose(np.asarray(got[0]),
                                       np.asarray(want[0]),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.asarray(got[1]),
                                       np.asarray(want[1]),
                                       rtol=1e-3, atol=1e-4)


def test_gradients_match_serial():
    mesh = mesh_lib.make_mesh(data=2, plane=4)
    rgb, sigma, xyz = _volume(1)
    cot_rgb = jnp.asarray(
        np.random.RandomState(2).normal(size=rgb.shape[:1] + (3,) +
                                        rgb.shape[3:]).astype(np.float32))

    def loss_dist(r, s, x):
        o_rgb, o_depth = plane_sharded_volume_render(
            r, s, x, mesh, z_mask=True, is_bg_depth_inf=False)
        return jnp.sum(o_rgb * cot_rgb) + 0.1 * jnp.sum(o_depth)

    def loss_ser(r, s, x):
        o_rgb, o_depth = _serial(r, s, x, True, False)
        return jnp.sum(o_rgb * cot_rgb) + 0.1 * jnp.sum(o_depth)

    g_dist = jax.grad(loss_dist, argnums=(0, 1, 2))(rgb, sigma, xyz)
    g_ser = jax.grad(loss_ser, argnums=(0, 1, 2))(rgb, sigma, xyz)
    for a, b, tol in zip(g_dist, g_ser, (1e-4, 1e-4, 1e-3)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=tol, atol=tol)


def test_train_step_plane_scan_matches_xla():
    """training.composite_backend=plane_scan on a plane-parallel mesh: the
    full train step matches the single-device XLA step numerically."""
    from mine_tpu.data.synthetic import make_batch
    from mine_tpu.train.step import SynthesisTrainer
    from tests.test_train import tiny_config, to_jnp

    cfg = tiny_config()
    cfg["data.per_gpu_batch_size"] = 4
    batch = to_jnp(make_batch(4, 64, 64, num_points=16))

    t_ref = SynthesisTrainer(cfg, steps_per_epoch=10)
    s0 = t_ref.init_state(batch_size=4)
    _, m_ref = t_ref.train_step(s0, batch)

    cfg_p = dict(cfg)
    cfg_p["training.composite_backend"] = "plane_scan"
    mesh = mesh_lib.make_mesh(data=4, plane=2)
    t_mesh = SynthesisTrainer(cfg_p, mesh=mesh, steps_per_epoch=10)
    s1 = t_mesh.init_state(batch_size=4)
    _, m_mesh = t_mesh.train_step(s1, batch)

    assert np.isfinite(float(m_mesh["loss"]))
    np.testing.assert_allclose(float(m_mesh["loss"]), float(m_ref["loss"]),
                               rtol=2e-3)


def test_single_plane_shard_degenerates_to_serial():
    """plane=1 mesh: the scan is just the serial composite under shard_map."""
    mesh = mesh_lib.make_mesh(data=8, plane=1)
    rgb, sigma, xyz = _volume(3, B=8, S=4)
    got = plane_sharded_volume_render(rgb, sigma, xyz, mesh,
                                      z_mask=False, is_bg_depth_inf=False)
    want = _serial(rgb, sigma, xyz, False, False)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-5)
