"""Analytic golden tests for MPI compositing — the invariants the reference's
stale visual tests encode (operations/test_rendering.py) turned into asserts,
plus a cross-check of the composite math against a direct torch port."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from mine_tpu import geometry
from mine_tpu.ops import rendering


def make_xyz(B, S, H, W, depths):
    """Fronto-parallel plane xyz with given depths (pinhole at center)."""
    disp = 1.0 / np.asarray(depths, dtype=np.float32)
    disp = np.tile(disp[None], (B, 1))
    K = jnp.asarray([[[20.0, 0, W / 2], [0, 20.0, H / 2], [0, 0, 1]]] * B)
    grid = geometry.pixel_grid_homogeneous(H, W)
    return geometry.plane_xyz_src(grid, jnp.asarray(disp), geometry.inverse_intrinsics(K))


def test_alpha_composition_opaque_front():
    B, K_, H, W = 1, 3, 4, 4
    alpha = jnp.zeros((B, K_, 1, H, W)).at[:, 0].set(1.0)
    vals = jnp.stack([jnp.full((B, 3, H, W), v) for v in (0.2, 0.5, 0.9)], axis=1)
    out, weights = rendering.alpha_composition(alpha, vals)
    np.testing.assert_allclose(np.asarray(out), 0.2, atol=1e-6)
    np.testing.assert_allclose(np.asarray(weights[:, 0]), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(weights[:, 1:]), 0.0, atol=1e-6)


def test_alpha_composition_two_planes():
    a0, a1 = 0.3, 0.6
    alpha = jnp.zeros((1, 2, 1, 2, 2)).at[:, 0].set(a0).at[:, 1].set(a1)
    vals = jnp.stack([jnp.full((1, 1, 2, 2), 1.0), jnp.full((1, 1, 2, 2), 2.0)],
                     axis=1)
    out, weights = rendering.alpha_composition(alpha, vals)
    w0, w1 = a0, (1 - a0) * a1
    np.testing.assert_allclose(np.asarray(out), w0 * 1.0 + w1 * 2.0, rtol=1e-6)


def test_volume_rendering_opaque_first_plane():
    """sigma -> inf on the first plane: output = plane rgb, depth = plane z."""
    B, S, H, W = 2, 4, 6, 8
    depths = [1.0, 2.0, 3.0, 4.0]
    xyz = make_xyz(B, S, H, W, depths)
    rgb = jnp.broadcast_to(
        jnp.asarray([0.1, 0.4, 0.7, 0.9])[None, :, None, None, None],
        (B, S, 3, H, W))
    sigma = jnp.zeros((B, S, 1, H, W)).at[:, 0].set(1e4)
    out, depth, t_acc, w = rendering.plane_volume_rendering(rgb, sigma, xyz, False)
    np.testing.assert_allclose(np.asarray(out), 0.1, atol=1e-3)
    # depth is the z of the first plane (== 1.0), weight-normalized
    np.testing.assert_allclose(np.asarray(depth), 1.0, rtol=1e-3)


def test_volume_rendering_transparent():
    B, S, H, W = 1, 3, 4, 4
    xyz = make_xyz(B, S, H, W, [1.0, 2.0, 3.0])
    rgb = jnp.ones((B, S, 3, H, W))
    sigma = jnp.zeros((B, S, 1, H, W))
    out, depth, t_acc, w = rendering.plane_volume_rendering(rgb, sigma, xyz, False)
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(t_acc[:, 0]), 1.0, atol=1e-5)


def torch_plane_volume_rendering(rgb, sigma, xyz):
    """Direct torch port of the reference formulas (mpi_rendering.py:42-67)."""
    rgb, sigma, xyz = map(torch.from_numpy, (rgb, sigma, xyz))
    B, S, _, H, W = sigma.shape
    diff = xyz[:, 1:] - xyz[:, :-1]
    dist = torch.norm(diff, dim=2, keepdim=True)
    dist = torch.cat([dist, torch.full((B, 1, 1, H, W), 1e3)], dim=1)
    transparency = torch.exp(-sigma * dist)
    alpha = 1 - transparency
    t_acc = torch.cumprod(transparency + 1e-6, dim=1)
    t_acc = torch.cat([torch.ones((B, 1, 1, H, W)), t_acc[:, :-1]], dim=1)
    weights = t_acc * alpha
    w_sum = weights.sum(1)
    rgb_out = (weights * rgb).sum(1)
    depth_out = (weights * xyz[:, :, 2:3]).sum(1) / (w_sum + 1e-5)
    return rgb_out.numpy(), depth_out.numpy(), weights.numpy()


def test_volume_rendering_matches_torch_port():
    rng = np.random.RandomState(0)
    B, S, H, W = 2, 5, 7, 9
    xyz = np.asarray(make_xyz(B, S, H, W, [1.0, 1.5, 2.0, 3.0, 5.0]))
    rgb = rng.uniform(size=(B, S, 3, H, W)).astype(np.float32)
    sigma = rng.uniform(0, 3, size=(B, S, 1, H, W)).astype(np.float32)
    out, depth, _, w = rendering.plane_volume_rendering(
        jnp.asarray(rgb), jnp.asarray(sigma), jnp.asarray(xyz), False)
    t_rgb, t_depth, t_w = torch_plane_volume_rendering(rgb, sigma, xyz)
    np.testing.assert_allclose(np.asarray(out), t_rgb, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(depth), t_depth, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(w), t_w, rtol=1e-4, atol=1e-5)


def test_bg_depth_inf_mode():
    B, S, H, W = 1, 2, 3, 3
    xyz = make_xyz(B, S, H, W, [1.0, 2.0])
    rgb = jnp.ones((B, S, 3, H, W))
    sigma = jnp.zeros((B, S, 1, H, W))  # fully transparent
    _, depth, _, _ = rendering.plane_volume_rendering(rgb, sigma, xyz, True)
    # all weight missing -> background depth ~1000
    np.testing.assert_allclose(np.asarray(depth), 1000.0, rtol=1e-2)


def test_render_tgt_identity_pose_matches_src_render():
    """Warping with the identity pose must reproduce the source-frame
    composite (and a full mask of S planes)."""
    rng = np.random.RandomState(1)
    B, S, H, W = 1, 4, 8, 12
    depths = [1.0, 2.0, 4.0, 8.0]
    disp = jnp.asarray(1.0 / np.asarray(depths, np.float32))[None]
    K = jnp.asarray([[[15.0, 0, W / 2], [0, 15.0, H / 2], [0, 0, 1]]])
    K_inv = geometry.inverse_intrinsics(K)
    grid = geometry.pixel_grid_homogeneous(H, W)
    xyz_src = geometry.plane_xyz_src(grid, disp, K_inv)

    rgb = jnp.asarray(rng.uniform(size=(B, S, 3, H, W)).astype(np.float32))
    sigma = jnp.asarray(rng.uniform(0.1, 2, size=(B, S, 1, H, W)).astype(np.float32))

    src_rgb, src_depth, _, _ = rendering.plane_volume_rendering(
        rgb, sigma, xyz_src, False)

    G = jnp.tile(jnp.eye(4), (B, 1, 1))
    res = rendering.render_tgt_rgb_depth(rgb, sigma, disp, G, K_inv, K)

    np.testing.assert_allclose(np.asarray(res.rgb), np.asarray(src_rgb),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(res.depth), np.asarray(src_depth),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(res.mask), float(S), atol=1e-6)


def test_render_tgt_behind_camera_sigma_zeroed():
    """Planes behind the target camera (z<0) must not contribute."""
    B, S, H, W = 1, 2, 4, 4
    depths = [1.0, 2.0]
    disp = jnp.asarray(1.0 / np.asarray(depths, np.float32))[None]
    K = jnp.asarray([[[10.0, 0, 2.0], [0, 10.0, 2.0], [0, 0, 1]]])
    K_inv = geometry.inverse_intrinsics(K)

    rgb = jnp.ones((B, S, 3, H, W))
    sigma = jnp.full((B, S, 1, H, W), 1e4)

    # translate the target camera far forward: both planes end up behind it
    G = jnp.eye(4)[None].at[0, 2, 3].set(-10.0)
    res = rendering.render_tgt_rgb_depth(rgb, sigma, disp, G, K_inv, K)
    np.testing.assert_allclose(np.asarray(res.rgb), 0.0, atol=1e-5)


def test_pallas_composite_untileable_h_pads_rows_exactly(monkeypatch):
    """Heights with no multiple-of-8 divisor (e.g. 756 full-res eval) keep
    the fused Pallas path via zero-padded rows sliced off the outputs —
    exact vs the XLA composite, values AND gradients (the pad/slice pair
    transposes cleanly through the custom VJP). A spy proves the Pallas
    path actually executed (no silent reroute to XLA)."""
    import mine_tpu.kernels.composite_vjp as cvjp
    from mine_tpu.kernels.composite import pallas_tileable

    calls = {"n": 0}
    real = cvjp.fused_volume_render_diff

    def spy(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(cvjp, "fused_volume_render_diff", spy)
    rng = np.random.RandomState(3)
    B, S, H, W = 1, 3, 12, 8  # 12 has no multiple-of-8 divisor
    assert not pallas_tileable(H) and pallas_tileable(W)
    depths = [1.0, 2.0, 4.0]
    disp = jnp.asarray(1.0 / np.asarray(depths, np.float32))[None]
    K = jnp.asarray([[[10.0, 0, W / 2], [0, 10.0, H / 2], [0, 0, 1]]])
    K_inv = geometry.inverse_intrinsics(K)
    rgb = jnp.asarray(rng.uniform(size=(B, S, 3, H, W)).astype(np.float32))
    sigma = jnp.asarray(
        rng.uniform(0.1, 2, size=(B, S, 1, H, W)).astype(np.float32))
    G = jnp.tile(jnp.eye(4), (B, 1, 1))

    def render(backend, r, s):
        return rendering.render_tgt_rgb_depth(r, s, disp, G,
                                              K_inv, K, backend=backend)

    ref = render("xla", rgb, sigma)
    out = render("pallas_diff", rgb, sigma)
    assert calls["n"] == 1, "pallas_diff was silently rerouted"
    np.testing.assert_allclose(np.asarray(out.rgb), np.asarray(ref.rgb),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out.depth), np.asarray(ref.depth),
                               rtol=1e-5, atol=1e-5)

    def loss(backend, r, s):
        res = render(backend, r, s)
        return jnp.mean(res.rgb) + 0.05 * jnp.mean(res.depth)

    g_ref = jax.grad(lambda r, s: loss("xla", r, s), argnums=(0, 1))(rgb, sigma)
    g_out = jax.grad(lambda r, s: loss("pallas_diff", r, s),
                     argnums=(0, 1))(rgb, sigma)
    for a, b in zip(g_out, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_render_use_alpha_dispatch():
    B, S, H, W = 1, 3, 4, 4
    xyz = make_xyz(B, S, H, W, [1.0, 2.0, 3.0])
    rgb = jnp.ones((B, S, 3, H, W)) * 0.5
    alpha = jnp.full((B, S, 1, H, W), 0.5)
    out, depth, blend, w = rendering.render(rgb, alpha, xyz, use_alpha=True)
    np.testing.assert_allclose(np.asarray(blend), 0.0)
    expect = 0.5 * (0.5 + 0.5 * 0.5 + 0.25 * 0.5)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5)


# ---------------------------------------------------------------------------
# The target-frame plane points in closed form (geometry.plane_xyz_tgt_at)
# against the route the reference takes: xyz warped as three more channels
# of a seven-channel volume.

# (B, S, H, W, t_z): at t_z = 0.3 the target camera stands back from the
# planes, so its border pixels sample outside the source on every border
XYZ_SHAPES = {
    "small_shift_16x24": (2, 4, 16, 24, 0.0),
    "every_border_32x32": (1, 6, 32, 32, 0.3),
}
WARP_BAND = 24


def _xyz_case(name):
    """Random in-band poses (small rotation about every axis, small shift),
    random planes, distinct intrinsics a camera."""
    B, S, H, W, t_z = XYZ_SHAPES[name]
    rng = np.random.RandomState(len(name))
    rgb = jnp.asarray(rng.uniform(size=(B, S, 3, H, W)).astype(np.float32))
    sigma = jnp.asarray(
        rng.uniform(0.1, 2.0, size=(B, S, 1, H, W)).astype(np.float32))
    disp = jnp.asarray(np.sort(
        rng.uniform(0.2, 1.0, size=(B, S)).astype(np.float32))[:, ::-1].copy())
    f = rng.uniform(0.9, 1.1, size=B) * W
    K = jnp.asarray(np.stack([
        np.array([[f[b], 0, W / 2 + 0.3], [0, f[b] * 1.05, H / 2 - 0.2],
                  [0, 0, 1]], np.float32) for b in range(B)]))
    G = np.tile(np.eye(4, dtype=np.float32), (B, 1, 1))
    for b in range(B):
        ax, ay, az = rng.uniform(-0.02, 0.02, size=3)
        Rx = np.array([[1, 0, 0], [0, np.cos(ax), -np.sin(ax)],
                       [0, np.sin(ax), np.cos(ax)]])
        Ry = np.array([[np.cos(ay), 0, np.sin(ay)], [0, 1, 0],
                       [-np.sin(ay), 0, np.cos(ay)]])
        Rz = np.array([[np.cos(az), -np.sin(az), 0],
                       [np.sin(az), np.cos(az), 0], [0, 0, 1]])
        G[b, :3, :3] = Rx @ Ry @ Rz
        G[b, :3, 3] = rng.uniform(-0.03, 0.03, size=3) + [0.0, 0.0, t_z]
    return rgb, sigma, disp, jnp.asarray(G), geometry.inverse_intrinsics(K), K


def _coords(disp, G, K_inv, K, H, W):
    from mine_tpu.ops import warp
    B, S = disp.shape

    def expand(x):
        return jnp.repeat(x, S, axis=0)
    return warp.homography_coords(
        (1.0 / disp).reshape(B * S), expand(G), expand(K_inv), expand(K),
        geometry.pixel_grid_homogeneous(H, W), (H, W))


def _seven_channel_render(rgb, sigma, disp, G, K_inv, K):
    """The plain reference: [rgb, sigma, xyz_tgt] gathered together in
    float32 (ops.warp.bilinear_sample), z-masked, composited in XLA."""
    from mine_tpu.ops import warp
    B, S, _, H, W = rgb.shape
    grid = geometry.pixel_grid_homogeneous(H, W)
    xyz_tgt = geometry.plane_xyz_tgt(
        geometry.plane_xyz_src(grid, disp, K_inv), G)
    volume = jnp.concatenate([rgb, sigma, xyz_tgt], axis=2)
    x, y, valid = _coords(disp, G, K_inv, K, H, W)
    warped = warp.bilinear_sample(volume.reshape(B * S, 7, H, W), x, y)
    warped = warped.reshape(B, S, 7, H, W)
    t_xyz = warped[:, :, 4:7]
    t_sigma = jnp.where(t_xyz[:, :, 2:3] >= 0.0, warped[:, :, 3:4], 0.0)
    out_rgb, out_depth, _, _ = rendering.render(warped[:, :, 0:3], t_sigma,
                                                t_xyz)
    mask = jnp.sum(valid.reshape(B, S, H, W).astype(jnp.float32), axis=1,
                   keepdims=True)
    return out_rgb, out_depth, mask


@pytest.mark.parametrize("shape", sorted(XYZ_SHAPES))
def test_closed_form_xyz_is_the_sampled_field(shape):
    """(a) depth_s (R K^-1) [xc, yc, 1] + t at the border-clipped source
    coordinates equals the float32 gather over the field plane_xyz_tgt
    builds, inside the image and on every border."""
    from mine_tpu.ops import warp
    rgb, _, disp, G, K_inv, K = _xyz_case(shape)
    B, S, _, H, W = rgb.shape
    x, y, _ = _coords(disp, G, K_inv, K, H, W)
    if shape.startswith("every_border"):
        xn, yn = np.asarray(x), np.asarray(y)
        assert (xn[:, :, 0] < 0).any() and (xn[:, :, -1] > W - 1).any()
        assert (yn[:, 0] < 0).any() and (yn[:, -1] > H - 1).any()
    field = geometry.plane_xyz_tgt(geometry.plane_xyz_src(
        geometry.pixel_grid_homogeneous(H, W), disp, K_inv), G)
    sampled = warp.bilinear_sample(field.reshape(B * S, 3, H, W), x, y)
    closed = geometry.plane_xyz_tgt_at(
        jnp.clip(x, 0.0, W - 1.0), jnp.clip(y, 0.0, H - 1.0),
        (1.0 / disp).reshape(B * S), jnp.repeat(G, S, axis=0),
        jnp.repeat(K_inv, S, axis=0))
    scale = float(jnp.max(jnp.abs(sampled)))
    np.testing.assert_allclose(np.asarray(closed), np.asarray(sampled),
                               rtol=1e-5, atol=1e-5 * scale)


# mesh_n 2: the shard_map branch a data-parallel train step takes (the
# coordinates are computed before the split and sampled per shard)
RENDER_ROUTES = [("xla", "xla", None), ("pallas_diff", "pallas_diff", None),
                 ("pallas_diff", "pallas_diff", 2)]


def _route_id(route):
    return route[0] + ("" if route[2] is None else f"_mesh{route[2]}")


def _render_route(route, rgb, sigma, disp, G, K_inv, K):
    warp_impl, backend, mesh_n = route
    mesh = None
    if mesh_n is not None:
        from mine_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.make_mesh(data=mesh_n, plane=1,
                                  devices=jax.devices()[:mesh_n])
    return rendering.render_tgt_rgb_depth(
        rgb, sigma, disp, G, K_inv, K, backend=backend, warp_impl=warp_impl,
        warp_band=WARP_BAND, mesh=mesh)


@pytest.mark.parametrize("route", RENDER_ROUTES, ids=_route_id)
@pytest.mark.parametrize("shape", sorted(XYZ_SHAPES))
def test_render_tgt_matches_seven_channel_reference(shape, route):
    """(b) rgb, depth and mask of the four-channel warp + closed-form xyz
    equal the seven-channel reference's, on the gather and on the Pallas
    pair (interpret mode), with the kernel on its fast path."""
    case = _xyz_case(shape)
    if route[2] is not None and case[0].shape[0] % route[2]:
        case = tuple(jnp.concatenate([a, a], axis=0) for a in case)
    ref_rgb, ref_depth, ref_mask = _seven_channel_render(*case)
    res = jax.jit(lambda *a: _render_route(route, *a))(*case)
    if route[0] == "pallas_diff":
        assert float(res.warp_in_domain) == 1.0  # no gather fallback
    np.testing.assert_allclose(np.asarray(res.rgb), np.asarray(ref_rgb),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res.depth), np.asarray(ref_depth),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(res.mask), np.asarray(ref_mask))


@pytest.mark.parametrize("route", RENDER_ROUTES[:2], ids=_route_id)
@pytest.mark.parametrize("shape", sorted(XYZ_SHAPES))
def test_render_tgt_gradients_match_seven_channel_reference(shape, route):
    """(c) d(scalar of rgb and depth) / d(rgb, sigma): the xyz channels never
    carried a gradient to the planes, so dropping them from the warp's
    backward changes nothing."""
    rgb, sigma, *cams = _xyz_case(shape)
    rng = np.random.RandomState(5)
    B, _, _, H, W = rgb.shape
    w_rgb = jnp.asarray(rng.normal(size=(B, 3, H, W)).astype(np.float32))
    w_depth = jnp.asarray(rng.normal(size=(B, 1, H, W)).astype(np.float32))

    def scalar(out_rgb, out_depth):
        return jnp.sum(out_rgb * w_rgb) + jnp.sum(out_depth * w_depth)

    def ours(r, s):
        res = _render_route(route, r, s, *cams)
        return scalar(res.rgb, res.depth)

    def reference(r, s):
        return scalar(*_seven_channel_render(r, s, *cams)[:2])

    got = jax.jit(jax.grad(ours, argnums=(0, 1)))(rgb, sigma)
    want = jax.jit(jax.grad(reference, argnums=(0, 1)))(rgb, sigma)
    for g, w in zip(got, want):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5 * scale)
