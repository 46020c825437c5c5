"""Serving engine (mine_tpu/serve): quantized MPI cache + render-only path.

The load-bearing contracts, each asserted here:
  * bf16 cache entries render BITWISE-identical to host-dequantized planes
    (dequant is a widening cast), per warp backend;
  * int8 dequant error is bounded by max|x|/254 per (plane, channel);
  * pose/entry padding to pow2 buckets never perturbs real rows;
  * the LRU byte budget evicts in recency order;
  * a serve-path cache miss warns ONCE, like the backend-fallback warning;
  * the engine-backed VideoGenerator.render_poses is bitwise-identical to
    the pre-engine private chunk loop it replaced (replicated verbatim
    below from git history).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mine_tpu import geometry
from mine_tpu.config import SERVE_WARP_BACKENDS, serve_config_from_dict
from mine_tpu.data.synthetic import SyntheticMPIDataset
from mine_tpu.ops import rendering
from mine_tpu.serve import (MicroBatcher, MPICache, PyramidCache,
                            RenderEngine, dequantize_planes, image_id_for,
                            pow2_bucket, quantize_planes)

H = W = 64
S = 4

# what serve.warp_backend may name, plus the forward-only kernel the video
# generator and the benchmark's serve cell hand the engine directly
ENGINE_WARP_IMPLS = SERVE_WARP_BACKENDS + ("pallas",)


@pytest.fixture(scope="module")
def scene():
    """One synthetic layered scene: planes [S,4,H,W] f32, disparity [S],
    K [3,3], plus a few in-band near poses."""
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


def _rng_planes(seed=0, scale=1.0):
    rng = np.random.RandomState(seed)
    return (rng.uniform(-1, 1, (S, 4, 8, 8)) * scale).astype(np.float32)


# ---------------- quantization ----------------

def test_pow2_bucket():
    assert [pow2_bucket(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]
    with pytest.raises(ValueError):
        pow2_bucket(0)


def test_bf16_roundtrip_deterministic():
    """bf16 dequant is a WIDENING cast: deterministic, idempotent, and
    exactly the f32 value of the bf16 storage."""
    planes = _rng_planes(1)
    q1, s1 = quantize_planes(planes, "bf16")
    q2, s2 = quantize_planes(planes, "bf16")
    assert q1.dtype == jnp.bfloat16 and s1 is None and s2 is None
    np.testing.assert_array_equal(np.asarray(q1, np.float32),
                                  np.asarray(q2, np.float32))
    d = dequantize_planes(q1, None)
    assert d.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(d), np.asarray(planes.astype(jnp.bfloat16),
                                  np.float32))
    # re-quantizing the dequantized form is a fixed point
    q3, _ = quantize_planes(np.asarray(d), "bf16")
    np.testing.assert_array_equal(np.asarray(q3, np.float32),
                                  np.asarray(q1, np.float32))


def test_int8_error_bound():
    """|dequant - x| <= scale/2 = max|x|/254 per (plane, channel) — the
    documented bound (serve/cache.py docstring)."""
    planes = _rng_planes(2, scale=3.7)
    q, scales = quantize_planes(planes, "int8")
    assert q.dtype == jnp.int8 and scales.shape == (S, 4, 1, 1)
    err = np.abs(np.asarray(dequantize_planes(q, scales)) - planes)
    bound = np.abs(planes).max(axis=(-1, -2), keepdims=True) / 254.0
    assert np.all(err <= bound + 1e-7), (err.max(), bound.max())


def test_int8_zero_plane_roundtrips_exact():
    planes = np.zeros((S, 4, 8, 8), np.float32)
    q, scales = quantize_planes(planes, "int8")
    np.testing.assert_array_equal(np.asarray(dequantize_planes(q, scales)),
                                  planes)


def test_unknown_quant_mode_rejected():
    with pytest.raises(ValueError):
        quantize_planes(_rng_planes(), "fp4")
    with pytest.raises(ValueError):
        MPICache(quant="fp4")


# ---------------- LRU cache ----------------

def _put(cache, key, seed):
    p = _rng_planes(seed)
    cache.put(key, p[:, 0:3], p[:, 3:4], np.linspace(1, .2, S, dtype=np.float32),
              np.eye(3, dtype=np.float32))


def test_lru_eviction_order_under_byte_budget():
    probe = MPICache(quant="float32")
    _put(probe, "x", 0)
    per_entry = probe.nbytes
    cache = MPICache(capacity_bytes=2 * per_entry, quant="float32")
    _put(cache, "a", 0)
    _put(cache, "b", 1)
    assert cache.keys() == ["a", "b"] and cache.evictions == 0
    _put(cache, "c", 2)  # over budget: evict LRU ("a")
    assert cache.keys() == ["b", "c"] and cache.evictions == 1
    assert cache.get("a") is None and cache.misses == 1
    # a get() refreshes recency, so the NEXT eviction takes "c"
    assert cache.get("b") is not None
    _put(cache, "d", 3)
    assert cache.keys() == ["b", "d"]
    assert cache.nbytes == 2 * per_entry


def test_lru_oversized_entry_still_stores():
    cache = MPICache(capacity_bytes=1, quant="float32")
    _put(cache, "big", 0)
    assert cache.keys() == ["big"]  # larger than budget, but never refused


def test_pyramid_cache_roundtrip_and_eviction():
    rng = np.random.RandomState(0)
    pyr = [rng.uniform(-1, 1, (S, 4, 8 >> i, 8 >> i)).astype(np.float32)
           for i in range(2)]
    disp = np.linspace(1, .2, S, dtype=np.float32)
    probe = PyramidCache(quant="float32")
    probe.put("x", pyr, disp)
    per_entry = probe.nbytes
    cache = PyramidCache(capacity_bytes=2 * per_entry, quant="float32")
    for key in ("a", "b", "c"):
        cache.put(key, pyr, disp)
    assert "a" not in cache and cache.evictions == 1
    got_pyr, got_disp = cache.get("b")
    for a, b in zip(got_pyr, pyr):
        np.testing.assert_array_equal(np.asarray(a), b)
    np.testing.assert_array_equal(np.asarray(got_disp), disp)


def test_image_id_is_content_addressed():
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    assert image_id_for(a) == image_id_for(a.copy())
    assert image_id_for(a) != image_id_for(a + 1)


# ---------------- engine parity ----------------

def _engine_for(scene, quant, **kw):
    engine = RenderEngine(cache=MPICache(quant=quant), **kw)
    p = scene["planes"]
    engine.put("img", p[:, 0:3], p[:, 3:4], scene["disparity"], scene["K"])
    return engine


@functools.partial(jax.jit, static_argnames=("warp_impl",))
def _reference_render(planes_S4HW, disp_S, K_33, G_44, warp_impl):
    """Per-pose render_tgt_rgb_depth on ALREADY-dequantized planes — the
    ground truth the engine's batched/bucketed/fused-dequant program must
    match bitwise."""
    rgb = planes_S4HW[None, :, 0:3]
    sigma = planes_S4HW[None, :, 3:4]
    disp = disp_S[None]
    K = K_33[None]
    K_inv = geometry.inverse_intrinsics(K)
    res = rendering.render_tgt_rgb_depth(
        rgb, sigma, disp, G_44[None], K_inv, K,
        use_alpha=False, is_bg_depth_inf=False, backend="xla",
        warp_impl=warp_impl, warp_band=48)
    return res.rgb[0], res.depth[0]


@pytest.mark.parametrize("impl", ENGINE_WARP_IMPLS)
def test_engine_matches_reference_bitwise_per_backend(scene, impl):
    """bf16 cache + fused in-jit dequant + pose batching + pow2 padding ==
    per-pose reference on host-dequantized planes, bitwise, for every warp
    backend (CPU: Pallas in interpret mode)."""
    engine = _engine_for(scene, "bf16", warp_band=48, max_bucket=4)
    deq = engine.cache.get("img").dequantized()
    rgb, depth = engine.render("img", scene["poses"], warp_impl=impl)
    for j, pose in enumerate(scene["poses"]):
        ref_rgb, ref_depth = _reference_render(
            deq, jnp.asarray(scene["disparity"]), jnp.asarray(scene["K"]),
            jnp.asarray(pose), impl)
        np.testing.assert_array_equal(rgb[j], np.asarray(ref_rgb))
        np.testing.assert_array_equal(depth[j], np.asarray(ref_depth))


@pytest.mark.parametrize("quant", ["float32", "bf16", "int8"])
def test_engine_pallas_matches_xla_backend(scene, quant):
    """The serve cell's engine (benchmark/traffic/gallery_steady.json:
    warp_impl "pallas", band 32 = infer/video.py WARP_BAND) against the
    default gather engine, per cache quant mode, on in-band poses; 5 poses
    pad to an 8-bucket. Two XLA programs around the same math: the banded
    kernel sums tent-weighted taps where the gather lerps."""
    from mine_tpu.infer.video import WARP_BAND
    rgb_x, dep_x = _engine_for(scene, quant, max_bucket=8).render(
        "img", scene["poses"])
    rgb_p, dep_p = _engine_for(scene, quant, max_bucket=8, warp_impl="pallas",
                               warp_band=WARP_BAND).render(
        "img", scene["poses"])
    np.testing.assert_allclose(rgb_p, rgb_x, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dep_p, dep_x, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("quant", ["float32", "int8"])
def test_engine_quant_modes_match_reference(scene, quant):
    """float32 and int8 caches: engine output == reference on the cache's
    own dequantized planes (bitwise — quantization error lives entirely in
    the storage, never in the render)."""
    engine = _engine_for(scene, quant, max_bucket=4)
    deq = engine.cache.get("img").dequantized()
    rgb, depth = engine.render("img", scene["poses"][:2])
    for j in range(2):
        ref_rgb, ref_depth = _reference_render(
            deq, jnp.asarray(scene["disparity"]), jnp.asarray(scene["K"]),
            jnp.asarray(scene["poses"][j]), "xla")
        np.testing.assert_array_equal(rgb[j], np.asarray(ref_rgb))
        np.testing.assert_array_equal(depth[j], np.asarray(ref_depth))


def test_engine_int8_render_error_bounded(scene):
    """End-to-end int8 error magnitude. The EXACT contract is elsewhere:
    per-plane dequant error <= max|x|/254 (test_int8_error_bound) and the
    render is bitwise-faithful to the int8-dequantized planes
    (test_engine_quant_modes_match_reference). What remains is how plane
    error propagates through compositing: this scene's sigma spans 0.05
    (transparent) to 60 (opaque), so near-transparent densities round to 0
    at scale max|sigma|/127 and blend weights shift by up to ~0.18. rgb
    output is a convex blend of in-[0,1] plane colors, so the shift bounds
    the worst pixel; typical pixels stay near the rgb dequant bound."""
    rgb8, _ = _engine_for(scene, "int8", max_bucket=4).render(
        "img", scene["poses"][:1])
    rgb32, _ = _engine_for(scene, "float32", max_bucket=4).render(
        "img", scene["poses"][:1])
    err = np.abs(rgb8 - rgb32)
    assert err.max() <= 0.25, err.max()
    # the 0.05 ambient density rounds to 0 EVERYWHERE, so the mean shift is
    # a few percent, not just the worst pixel
    assert err.mean() <= 0.05, err.mean()


def test_padded_bucket_invariance(scene):
    """P=3 poses pad to a 4-bucket; the same poses rendered one-by-one
    (1-buckets) must agree bitwise — padding never perturbs real rows."""
    engine = _engine_for(scene, "bf16", max_bucket=4)
    rgb, depth = engine.render("img", scene["poses"][:3])
    for j in range(3):
        rgb1, depth1 = engine.render("img", scene["poses"][j:j + 1])
        np.testing.assert_array_equal(rgb[j], rgb1[0])
        np.testing.assert_array_equal(depth[j], depth1[0])


def test_render_many_coalesces_distinct_entries(scene):
    """Interleaved requests against two cached MPIs in ONE device call ==
    per-entry single renders, bitwise; entry padding (R=2 -> bucket 2,
    idx gather) must not leak across rows."""
    engine = _engine_for(scene, "bf16", max_bucket=8)
    p2 = scene["planes"][::-1].copy()  # a distinct second scene
    engine.put("img2", p2[:, 0:3], p2[:, 3:4], scene["disparity"],
               scene["K"])
    reqs = [("img", scene["poses"][0]), ("img2", scene["poses"][1]),
            ("img", scene["poses"][2])]
    calls_before = engine.device_calls
    out = engine.render_many(reqs)
    assert engine.device_calls == calls_before + 1
    for (iid, pose), (rgb, depth) in zip(reqs, out):
        ref_rgb, ref_depth = engine.render(iid, pose[None])
        np.testing.assert_array_equal(rgb, ref_rgb[0])
        np.testing.assert_array_equal(depth, ref_depth[0])


def test_cache_miss_warns_once_then_encodes(scene):
    """A render-path miss must run the synchronous encode AND warn exactly
    once per engine (the _warn_backend_fallback pattern)."""
    import warnings as _w

    from mine_tpu.serve import engine as engine_mod

    p = scene["planes"]

    def encode_fn(img):
        return p[:, 0:3], p[:, 3:4], scene["disparity"], scene["K"]

    engine = RenderEngine(cache=MPICache(quant="bf16"), max_bucket=4,
                          encode_fn=encode_fn)
    # the once-only set is keyed by id(engine); a gc'd engine from an
    # earlier test could have recycled this id — make the slate clean
    engine_mod._warned_sync_encode.discard(id(engine))
    img = np.zeros((4, 4, 3), np.float32)
    with pytest.warns(UserWarning, match="SYNCHRONOUS encode"):
        engine.render("miss1", scene["poses"][:1], image=img)
    assert "miss1" in engine.cache
    # second miss on the SAME engine: silent (one-time notice)
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        engine.render("miss2", scene["poses"][:1], image=img)
    assert not any("SYNCHRONOUS" in str(r.message) for r in rec)


def test_cache_miss_without_encode_fn_raises(scene):
    engine = _engine_for(scene, "bf16")
    with pytest.raises(KeyError):
        engine.render("nope", scene["poses"][:1])


def test_engine_rejects_non_pow2_bucket():
    with pytest.raises(ValueError):
        RenderEngine(max_bucket=6)


# ---------------- micro-batcher ----------------

def test_batcher_coalesces_and_resolves_in_order(scene):
    engine = _engine_for(scene, "bf16", max_bucket=8)
    p2 = scene["planes"][::-1].copy()
    engine.put("img2", p2[:, 0:3], p2[:, 3:4], scene["disparity"],
               scene["K"])
    batcher = MicroBatcher(engine, max_requests=8, max_wait_ms=0.0,
                           start=False)  # no thread: deterministic flush
    futs = [batcher.submit("img", scene["poses"][0]),
            batcher.submit("img2", scene["poses"][1]),
            batcher.submit("img", scene["poses"][2])]
    calls_before = engine.device_calls
    assert batcher.flush() == 3
    assert engine.device_calls == calls_before + 1  # coalesced
    for fut, (iid, pose) in zip(futs, [("img", scene["poses"][0]),
                                       ("img2", scene["poses"][1]),
                                       ("img", scene["poses"][2])]):
        rgb, depth = fut.result(timeout=5)
        ref_rgb, ref_depth = engine.render(iid, pose[None])
        np.testing.assert_array_equal(rgb, ref_rgb[0])
        np.testing.assert_array_equal(depth, ref_depth[0])


def test_batcher_thread_drains_on_close(scene):
    engine = _engine_for(scene, "bf16", max_bucket=4)
    batcher = MicroBatcher(engine, max_requests=2, max_wait_ms=50.0)
    futs = [batcher.submit("img", scene["poses"][j]) for j in range(3)]
    for f in futs:
        assert f.result(timeout=10)[0].shape == (3, H, W)
    batcher.close()


# ---------------- config ----------------

def test_serve_config_validation():
    base = {"serve.cache_bytes": 0, "serve.cache_quant": "bf16",
            "serve.max_bucket": 8, "serve.max_requests": 8,
            "serve.max_wait_ms": 2.0, "serve.eval_encode_once": False,
            "serve.eval_cache_quant": "float32"}
    cfg = serve_config_from_dict(base)
    assert cfg.cache_quant == "bf16" and cfg.max_bucket == 8
    for bad in ({"serve.cache_quant": "fp4"}, {"serve.max_bucket": 6},
                {"serve.max_requests": 0}, {"serve.max_wait_ms": -1},
                {"serve.cache_bytes": -2}, {"serve.eval_cache_quant": "x"}):
        with pytest.raises(ValueError):
            serve_config_from_dict(dict(base, **bad))


# ---------------- video path ----------------

def _legacy_render_poses(gen, poses_F44, chunk):
    """VERBATIM replication of the pre-engine VideoGenerator chunk loop
    (git history: _render_chunk_impl + render_poses) — the bitwise baseline
    the engine-backed path must reproduce."""
    @functools.partial(jax.jit, static_argnames=("warp_impl",))
    def render_chunk(G_tgt_src_F44, warp_impl):
        F = G_tgt_src_F44.shape[0]

        def tile(x):
            return jnp.broadcast_to(x, (F,) + x.shape[1:])

        res = rendering.render_tgt_rgb_depth(
            tile(gen.mpi_rgb), tile(gen.mpi_sigma),
            tile(gen.disparity), G_tgt_src_F44,
            tile(gen.K_inv), tile(gen.K),
            use_alpha=gen.cfg.use_alpha,
            is_bg_depth_inf=gen.cfg.is_bg_depth_inf,
            backend=gen.backend,
            warp_impl=warp_impl,
            warp_band=32)
        return res.rgb, 1.0 / jnp.maximum(res.depth, 1e-8)

    F = poses_F44.shape[0]
    rgbs, disps = [], []
    for i in range(0, F, chunk):
        c = poses_F44[i:i + chunk]
        pad = 0
        if c.shape[0] < chunk:
            pad = chunk - c.shape[0]
            c = np.concatenate(
                [c, np.tile(np.eye(4, dtype=np.float32), (pad, 1, 1))],
                axis=0)
        rgb, disp = render_chunk(jnp.asarray(c), "xla")
        rgb, disp = np.asarray(rgb), np.asarray(disp)
        if pad:
            rgb, disp = rgb[:-pad], disp[:-pad]
        rgbs.append(rgb)
        disps.append(disp)
    return np.concatenate(rgbs), np.concatenate(disps)


def test_video_render_poses_bitwise_matches_legacy_chunk_loop(scene):
    """Satellite gate: VideoGenerator frames through the serving engine
    (float32 cache) are BITWISE-unchanged vs the replaced private chunk
    loop — including the remainder chunk, which the old loop padded to
    `chunk` and the engine buckets to the next pow2."""
    from mine_tpu.config import mpi_config_from_dict
    from mine_tpu.infer.video import VideoGenerator
    from tests.test_train import tiny_config

    gen = VideoGenerator.__new__(VideoGenerator)
    gen.cfg = mpi_config_from_dict(tiny_config())
    gen.config = {}
    gen.backend = "xla"
    gen.chunk = 8
    gen.K = jnp.asarray(scene["K"])[None]
    gen.K_inv = geometry.inverse_intrinsics(gen.K)
    gen.mpi_rgb = jnp.asarray(scene["planes"][:, 0:3])[None]
    gen.mpi_sigma = jnp.asarray(scene["planes"][:, 3:4])[None]
    gen.disparity = jnp.asarray(scene["disparity"])[None]
    gen.img = jnp.zeros((1, H, W, 3))
    engine = RenderEngine(
        use_alpha=gen.cfg.use_alpha, is_bg_depth_inf=gen.cfg.is_bg_depth_inf,
        backend="xla", warp_band=32, max_bucket=8,
        cache=MPICache(quant="float32"))
    gen.engine = engine
    gen.image_id = image_id_for(np.asarray(gen.img))
    engine.put(gen.image_id, gen.mpi_rgb[0], gen.mpi_sigma[0],
               gen.disparity[0], gen.K[0])

    poses = np.tile(np.eye(4, dtype=np.float32), (11, 1, 1))
    poses[:, 0, 3] = np.linspace(0.0, 0.05, 11)
    poses[:, 2, 3] = np.linspace(0.0, -0.08, 11)

    rgb_new, disp_new = gen.render_poses(poses)
    rgb_old, disp_old = _legacy_render_poses(gen, poses, chunk=8)
    np.testing.assert_array_equal(rgb_new, rgb_old)
    np.testing.assert_array_equal(disp_new, disp_old)
