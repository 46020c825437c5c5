"""Fused Pallas render megakernel (kernels/render_fused.py + the
"pallas_fused" warp backend).

The load-bearing contracts, each asserted here:
  * the megakernel (warp -> in-kernel dequant -> composite -> blend in one
    pass) matches the XLA dequant+gather+composite graph within house
    kernel tolerances — the measured CPU-interpreter divergence is
    <= 1.8e-7 rgb / 1.5e-6 depth (FMA/fusion-order ulps, never structure);
  * the dequant LOCATION is free: reading the CACHED (int8/bf16/f32)
    planes inside the kernel is BITWISE-identical to pre-dequantizing the
    same planes and running them through the same kernel, for all three
    cache quant modes — so the int8 round-trip bound |w - dq| <= scale/2
    survives the fused read unchanged;
  * the guard (fused_domain_ok + the lax.cond fallback) keeps out-of-band
    poses exact via the XLA branch and reports the fast-path fraction;
  * the custom-VJP twin backprops the XLA-equivalent graph: grads through
    the guarded kernel match grads through the reference;
  * the serve engines render identically through warp_impl="pallas_fused"
    vs the default XLA path — every cache quant mode, single-device and
    1x1/2x1/2x2/4x1 serve meshes with padded pose buckets — and the mesh
    fused program is BITWISE the single-device fused program;
  * the whole request is ONE kernel: the audited serve_render_fused
    program stages exactly one pallas_call and takes the int8 cache in
    un-dequantized (no separate dequant program), and a deliberately
    UNFUSED build of the same program trips the dot_budget gate.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mine_tpu.kernels.render_fused import (fused_domain_ok,
                                           fused_plane_render,
                                           fused_plane_render_guarded,
                                           xla_reference_render)
from mine_tpu.serve import MeshRenderEngine, MPICache, RenderEngine
from mine_tpu.serve.cache import quantize_planes

# house kernel-vs-XLA tolerances (tests/test_warp_kernel.py lineage);
# measured fused-vs-xla divergence at these fixtures: rgb <= 1.79e-7,
# depth <= 1.43e-6
RGB_TOL = dict(rtol=1e-5, atol=1e-6)
DEPTH_TOL = dict(rtol=1e-4, atol=1e-5)

H = W = 64
S = 4


# ---------------- kernel-level fixture (synthetic coords) ----------------

@pytest.fixture(scope="module")
def kin():
    """Near-identity per-plane warps over a [2,4,16,128] volume: every
    row-block's source span fits a 16-row band, so the guard admits the
    kernel; W=128 keeps the lane tile exact (no pad columns in play)."""
    rng = np.random.RandomState(0)
    B, S_, Hs, Ws = 2, 4, 16, 128
    vol = rng.uniform(-1, 1, (B, S_, 4, Hs, Ws)).astype(np.float32)
    vol[:, :, 3] = np.abs(vol[:, :, 3])  # nonnegative density
    xyz = rng.uniform(-1, 1, (B, S_, 3, Hs, Ws)).astype(np.float32)
    xyz[:, :, 2] += 2.0                  # in front of the camera
    cx = (np.arange(Ws)[None, None, None, :]
          + rng.uniform(-1.5, 1.5, (B, S_, Hs, 1))).astype(np.float32)
    cy = (np.arange(Hs)[None, None, :, None]
          + rng.uniform(-1.5, 1.5, (B, S_, 1, Ws))).astype(np.float32)
    return {"vol": vol, "xyz": xyz,
            "cx": np.broadcast_to(cx, (B, S_, Hs, Ws)).copy(),
            "cy": np.broadcast_to(cy, (B, S_, Hs, Ws)).copy()}


def _fused(vol, scales, kin, band=16):
    r, d = fused_plane_render(vol, scales, kin["xyz"], kin["cx"], kin["cy"],
                              band=band, rows_per_block=8, interpret=True)
    return np.asarray(r), np.asarray(d)


def _reference(vol, scales, kin):
    r, d = jax.jit(lambda v, sc, x, a, b:
                   xla_reference_render(v, sc, x, a, b))(
                       vol, scales, kin["xyz"], kin["cx"], kin["cy"])
    return np.asarray(r), np.asarray(d)


def test_fused_matches_xla_reference(kin):
    assert bool(fused_domain_ok(kin["vol"].shape, kin["vol"].dtype,
                                jnp.asarray(kin["cy"]), band=16))
    r_f, d_f = _fused(kin["vol"], None, kin)
    r_x, d_x = _reference(kin["vol"], None, kin)
    np.testing.assert_allclose(r_f, r_x, **RGB_TOL)
    np.testing.assert_allclose(d_f, d_x, **DEPTH_TOL)


@pytest.mark.parametrize("quant", ["float32", "bf16", "int8"])
def test_in_kernel_dequant_bitwise_vs_pre_dequant(kin, quant):
    """The tentpole's dequant pin: the quantized planes through the kernel
    (scales in SMEM, dequant in registers) equal the pre-dequantized f32
    planes through the SAME kernel exactly — the bf16 widen commutes with
    the fused read bit-for-bit.

    int8 is held to the house kernel tolerances instead. Under jax 0.9 the
    interpreter compiles the int8-input kernel and the f32-input kernel as
    two XLA:CPU programs whose tent matmuls accumulate differently: every
    output is a sum of two non-zero products, and fma(a, b, round(c*d))
    and round(a*b) + round(c*d) differ by one ulp (measured: 47% of
    elements, max 1.8e-7). It is not the scale multiply that moves — the
    mismatch is unchanged with exact power-of-two scales — so what the pin
    is about, where the dequant happens, is asserted bitwise in a form
    both programs share: the dequantized volume through the in-kernel
    multiply with unit scales equals it through the no-dequant branch."""
    q, scales = quantize_planes(jnp.asarray(kin["vol"][0]), quant)
    q = jnp.asarray(q)[None].repeat(2, axis=0)
    if scales is not None:
        scales = jnp.asarray(scales)[None].repeat(2, axis=0)
    dq = q.astype(jnp.float32)
    if scales is not None:
        dq = dq * scales
    r_q, d_q = _fused(np.asarray(q), scales, kin)
    r_dq, d_dq = _fused(np.asarray(dq), None, kin)
    if quant == "int8":
        np.testing.assert_allclose(r_q, r_dq, **RGB_TOL)
        np.testing.assert_allclose(d_q, d_dq, **DEPTH_TOL)
        r_1, d_1 = _fused(np.asarray(dq), jnp.ones_like(scales), kin)
        np.testing.assert_array_equal(r_1, r_dq)
        np.testing.assert_array_equal(d_1, d_dq)
    else:
        np.testing.assert_array_equal(r_q, r_dq)
        np.testing.assert_array_equal(d_q, d_dq)


def test_int8_roundtrip_bound_survives_fused_read(kin):
    """|w - dq| <= scale/2 per element (symmetric round-to-nearest, no
    clipping past amax), and the fused read returns exactly the dq values
    (previous test) — so the bound holds through the megakernel too."""
    w = jnp.asarray(kin["vol"][0])
    q, scales = quantize_planes(w, "int8")
    dq = np.asarray(q, np.float32) * np.asarray(scales)
    bound = np.broadcast_to(np.asarray(scales) / 2.0, dq.shape)
    np.testing.assert_array_less(np.abs(np.asarray(w) - dq),
                                 bound + 1e-7)


# ---------------- guard + fallback ----------------

def test_guard_in_domain_is_bitwise_the_kernel(kin):
    r_f, d_f = _fused(kin["vol"], None, kin)
    r_g, d_g, ok = jax.jit(
        lambda v, x, a, b: fused_plane_render_guarded(
            v, None, x, a, b, band=16, interpret=True))(
                kin["vol"], kin["xyz"], kin["cx"], kin["cy"])
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(r_g), r_f)
    np.testing.assert_array_equal(np.asarray(d_g), d_f)


def test_guard_falls_back_out_of_band(kin):
    """A single row-block whose source span exceeds the band flips the
    guard; the cond's slow branch is the XLA graph, so values stay right
    (house tolerances — different fusion context than a standalone jit)."""
    cy = kin["cy"].copy()
    cy[0, 0, 0, 0] = 0.0
    cy[0, 0, 0, 1] = 15.0  # 15-row span inside one 8-row block
    r_g, d_g, ok = jax.jit(
        lambda v, x, a, b: fused_plane_render_guarded(
            v, None, x, a, b, band=8, interpret=True))(
                kin["vol"], kin["xyz"], kin["cx"], cy)
    assert not bool(ok)
    r_x, d_x = jax.jit(lambda v, x, a, b:
                       xla_reference_render(v, None, x, a, b))(
                           kin["vol"], kin["xyz"], kin["cx"], cy)
    np.testing.assert_allclose(np.asarray(r_g), np.asarray(r_x), **RGB_TOL)
    np.testing.assert_allclose(np.asarray(d_g), np.asarray(d_x), **DEPTH_TOL)


def test_guard_static_row_block_mismatch_never_stages_kernel(kin):
    """H_t not divisible by rows_per_block is a STATIC domain miss: the
    guarded wrapper must return the XLA path without tracing the kernel
    (lax.cond traces both branches, and the kernel asserts the tiling)."""
    r_g, d_g, ok = fused_plane_render_guarded(
        kin["vol"], None, kin["xyz"], kin["cx"], kin["cy"],
        band=16, rows_per_block=7, interpret=True)
    assert not bool(ok)
    r_x, d_x = xla_reference_render(kin["vol"], None, kin["xyz"],
                                    kin["cx"], kin["cy"])
    np.testing.assert_array_equal(np.asarray(r_g), np.asarray(r_x))
    np.testing.assert_array_equal(np.asarray(d_g), np.asarray(d_x))
    assert not bool(fused_domain_ok(kin["vol"].shape, kin["vol"].dtype,
                                    jnp.asarray(kin["cy"]), band=16,
                                    rows_per_block=7))


def test_guarded_grads_match_reference(kin):
    """The custom-VJP twin: forward is the megakernel, backward is the
    XLA-equivalent graph — grads match autodiff through the reference."""
    vol, xyz = jnp.asarray(kin["vol"]), jnp.asarray(kin["xyz"])
    cx, cy = jnp.asarray(kin["cx"]), jnp.asarray(kin["cy"])

    def loss(v, x):
        r, d, _ = fused_plane_render_guarded(v, None, x, cx, cy,
                                             band=16, interpret=True)
        return jnp.sum(r) + jnp.sum(d)

    def ref_loss(v, x):
        r, d = xla_reference_render(v, None, x, cx, cy)
        return jnp.sum(r) + jnp.sum(d)

    g_v, g_x = jax.grad(loss, argnums=(0, 1))(vol, xyz)
    r_v, r_x = jax.grad(ref_loss, argnums=(0, 1))(vol, xyz)
    assert bool(jnp.isfinite(g_v).all() & jnp.isfinite(g_x).all())
    np.testing.assert_allclose(np.asarray(g_v), np.asarray(r_v),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g_x), np.asarray(r_x),
                               rtol=1e-4, atol=1e-6)


# ---------------- serve engines through the fused backend ----------------

@pytest.fixture(scope="module")
def scene():
    """The test_serve_fleet.py scene: one synthetic layered entry, 5 poses
    (padded to an 8-bucket by the engines)."""
    from mine_tpu.data.synthetic import SyntheticMPIDataset

    ds = SyntheticMPIDataset(seed=3, height=H, width=W, num_planes_gt=S)
    planes = np.concatenate([np.asarray(ds.mpi_rgb[0]),
                             np.asarray(ds.mpi_sigma[0])], axis=1)
    poses = np.tile(np.eye(4, dtype=np.float32), (5, 1, 1))
    poses[:, 0, 3] = np.linspace(0.0, 0.04, 5)
    poses[:, 2, 3] = np.linspace(0.0, -0.06, 5)
    return {"planes": planes.astype(np.float32),
            "disparity": np.asarray(ds.disparity[0]),
            "K": np.asarray(ds.K, np.float32),
            "poses": poses}


def _engine(scene, quant, warp_impl, mesh=None):
    # warp_band=64 = full source height: the band covers any in-image
    # coords, so the guard's alignment slack is zero for every cache dtype
    # and the fused fast path is live even for the int8 (32-row tile) cache
    kw = dict(cache=MPICache(quant=quant), max_bucket=8,
              warp_impl=warp_impl, warp_band=64)
    if mesh is None:
        eng = RenderEngine(**kw)
    else:
        eng = MeshRenderEngine(mesh_batch=mesh[0], mesh_model=mesh[1], **kw)
    p = scene["planes"]
    eng.put("img", p[:, 0:3], p[:, 3:4], scene["disparity"], scene["K"])
    return eng


@pytest.mark.parametrize("quant", ["float32", "bf16", "int8"])
def test_engine_fused_matches_xla_backend(scene, quant):
    """warp_impl="pallas_fused" vs the default XLA dequant+gather+composite
    on the single-device engine, per cache quant mode. House tolerances:
    the two are different XLA programs around the same math (measured
    divergence <= 1.8e-7 rgb / 1.5e-6 depth at this fixture)."""
    rgb_x, dep_x = _engine(scene, quant, "xla").render("img", scene["poses"])
    rgb_f, dep_f = _engine(scene, quant, "pallas_fused").render(
        "img", scene["poses"])
    np.testing.assert_allclose(rgb_f, rgb_x, **RGB_TOL)
    np.testing.assert_allclose(dep_f, dep_x, **DEPTH_TOL)


@pytest.mark.parametrize("mesh", [(1, 1), (2, 1), (2, 2), (4, 1)])
def test_mesh_engine_fused_bitwise_matches_single_fused(scene, mesh):
    """The fused mesh program (shard_map over the serve "batch" axis) is
    BITWISE the single-device fused program — int8 so the SMEM scales ride
    the shard_map too — and stays within house tolerances of the XLA mesh
    path."""
    single = _engine(scene, "int8", "pallas_fused")
    fleet = _engine(scene, "int8", "pallas_fused", mesh=mesh)
    assert fleet.num_devices() == mesh[0] * mesh[1]
    rgb_s, dep_s = single.render("img", scene["poses"])
    rgb_m, dep_m = fleet.render("img", scene["poses"])
    np.testing.assert_array_equal(rgb_m, rgb_s)
    np.testing.assert_array_equal(dep_m, dep_s)
    rgb_x, dep_x = _engine(scene, "int8", "xla", mesh=mesh).render(
        "img", scene["poses"])
    np.testing.assert_allclose(rgb_m, rgb_x, **RGB_TOL)
    np.testing.assert_allclose(dep_m, dep_x, **DEPTH_TOL)


# ---------------- one-kernel structure + the audit gate ----------------

def test_serve_render_fused_is_one_kernel():
    """The audited program (analysis/programs.py serve_render_fused) stages
    exactly ONE pallas_call — warp, dequant, composite and blend never
    split back into separate programs — and the int8 cache crosses the jit
    boundary un-dequantized (the float volume never exists outside the
    kernel)."""
    from mine_tpu.analysis.flops import iter_eqns
    from mine_tpu.analysis.programs import get_program

    prog = get_program("serve_render_fused")
    jaxpr = prog.jaxpr()
    n_pallas = sum(1 for e in iter_eqns(jaxpr)
                   if e.primitive.name == "pallas_call")
    assert n_pallas == 1, f"expected one fused kernel, saw {n_pallas}"
    in_dtypes = [v.aval.dtype for v in jaxpr.jaxpr.invars
                 if hasattr(v.aval, "dtype")]
    assert any(dt == jnp.int8 for dt in in_dtypes), (
        "int8 cache should enter the program un-dequantized")


def test_unfused_variant_trips_dot_budget():
    """Satellite 6's seeded violation: the SAME serve program built without
    the megakernel (warp_impl="xla" over the int8 cache) measured against
    serve_render_fused's committed baseline must FAIL dot_budget — the
    gate actually pins the one-kernel structure, not just a number."""
    from mine_tpu.analysis.framework import load_baseline
    from mine_tpu.analysis.passes import DotBudgetPass
    from mine_tpu.analysis.programs import serve_render_program

    unfused = serve_render_program("int8", None, "serve_render_fused", "xla")
    result = DotBudgetPass(load_baseline()).run(unfused)
    assert result.ok is False, (
        "an unfused build matched the fused baseline — dot_budget is "
        "blind to the fusion this program exists to pin")
    assert result.details
