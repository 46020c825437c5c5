"""Sharded serving fleet (mine_tpu/serve/shardmap.py + fleet.py).

The load-bearing contracts, each asserted here:
  * the mesh render program is BITWISE-identical to the single-device
    engine on 1/2/4/8-device CPU meshes, per quant mode, including padded
    pose/entry buckets (the per-pose-independent program shards cleanly
    along "batch"; 8x1/4x2 graduated from the GSPMD xfail marker once
    measured bitwise-clean — only the TRAIN step still diverges at 8);
  * key-range ownership is a pure function of (image_id, num_shards):
    deterministic, contiguous ranges, every shard reachable;
  * `ShardedPlaneCache` routes lookups to the owner shard, places encodes
    owner-side under per-shard budgets, and `rebalance` moves exactly the
    entries whose range changed;
  * `ContinuousBatcher` dispatches on full-bucket OR oldest-deadline and
    counts which trigger fired;
  * `ServeFleet` wires the three per the serve.* config keys and its
    serve.shard.* events pass the strict mtpu-ev1 schema.
"""

import time

import numpy as np
import pytest

import jax

from mine_tpu.config import serve_config_from_dict
from mine_tpu.data.synthetic import SyntheticMPIDataset
from mine_tpu.serve import (ContinuousBatcher, MeshRenderEngine, MPICache,
                            RenderEngine, ServeFleet, ShardedPlaneCache,
                            make_serve_mesh, render_shardings,
                            shard_for_key)
from mine_tpu.serve.shardmap import SERVE_BATCH_AXIS, SERVE_MODEL_AXIS
from mine_tpu.telemetry import events as tevents

H = W = 64
S = 4


@pytest.fixture(scope="module")
def scene():
    """One synthetic layered scene (same construction as test_serve.py)."""
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


def _put_scene(engine, scene, key="img"):
    p = scene["planes"]
    engine.put(key, p[:, 0:3], p[:, 3:4], scene["disparity"], scene["K"])
    return engine


def _rng_planes(seed=0):
    rng = np.random.RandomState(seed)
    return rng.uniform(-1, 1, (S, 4, 8, 8)).astype(np.float32)


def _put_rand(cache, key, seed):
    p = _rng_planes(seed)
    return cache.put(key, p[:, 0:3], p[:, 3:4],
                     np.linspace(1, .2, S, dtype=np.float32),
                     np.eye(3, dtype=np.float32))


# ---------------- key-range ownership ----------------

def test_shard_for_key_deterministic_range_partition():
    """Hex-prefixed ids land by their leading 32 bits: shard s owns the
    contiguous range [s*2^32/N, (s+1)*2^32/N)."""
    assert shard_for_key("00000000aa", 4) == 0
    assert shard_for_key("3fffffffaa", 4) == 0
    assert shard_for_key("40000000aa", 4) == 1
    assert shard_for_key("ffffffffaa", 4) == 3
    # deterministic: pure function of (id, num_shards)
    for iid in ("0badcafe00", "deadbeef99", "not-a-hex-id"):
        assert shard_for_key(iid, 8) == shard_for_key(iid, 8)
    with pytest.raises(ValueError):
        shard_for_key("00aa", 0)


def test_shard_for_key_contiguous_and_covering():
    """Sorting ids by key position gives nondecreasing shard owners
    (contiguous ranges), every shard is reachable, and 1 shard owns all."""
    ids = ["%08x" % (i * 2654435761 % (1 << 32)) for i in range(256)]
    for n in (1, 2, 3, 4, 8):
        owners = [shard_for_key(i, n) for i in sorted(ids)]
        assert owners == sorted(owners), f"non-contiguous at N={n}"
        assert set(owners) == set(range(n)), f"unreachable shard at N={n}"
    assert all(shard_for_key(i, 1) == 0 for i in ids)


def test_shard_for_key_string_fallback():
    """Non-hex ids (tests, benches) hash the id string — still
    deterministic and in range."""
    for n in (2, 4):
        s = shard_for_key("bench", n)
        assert 0 <= s < n
        assert shard_for_key("bench", n) == s


# ---------------- mesh + shardings ----------------

def test_make_serve_mesh_shapes_and_validation():
    mesh = make_serve_mesh(2, 2)
    assert mesh.shape == {SERVE_BATCH_AXIS: 2, SERVE_MODEL_AXIS: 2}
    with pytest.raises(ValueError):
        make_serve_mesh(3, 1)  # non-pow2
    with pytest.raises(ValueError):
        make_serve_mesh(16, 1)  # more than the 8 virtual devices


def test_render_shardings_specs():
    from jax.sharding import PartitionSpec as P
    s1 = render_shardings(make_serve_mesh(4, 1))
    assert s1["planes"].spec == P()          # model axis 1: replicated
    assert s1["G"].spec == P(SERVE_BATCH_AXIS)
    assert s1["out"].spec == P(SERVE_BATCH_AXIS)
    s2 = render_shardings(make_serve_mesh(2, 2))
    assert s2["planes"].spec == P(None, SERVE_MODEL_AXIS)
    assert s2["K"].spec == P()


@pytest.mark.parametrize("quant", ["bf16", "int8", "float32"])
@pytest.mark.parametrize("mesh", [(1, 1), (2, 1), (2, 2), (4, 1),
                                  (8, 1), (4, 2)])
def test_mesh_render_bitwise_matches_single_device(scene, mesh, quant):
    """The acceptance bar: the ONE jitted mesh render program with
    NamedSharding specs is bitwise-identical to the single-device engine —
    every mesh shape x quant mode, on P=5 poses padded to an 8-bucket.

    8x1 and 4x2 used to sit under the 8-device GSPMD xfail marker
    (ROADMAP 'Mesh-vs-single numeric divergence at 8 CPU devices'); the
    per-pose-independent RENDER program measured bitwise-clean on both, so
    they graduated to plain parity cases. The TRAIN-step divergence remains
    tracked separately — only render is promoted here."""
    mb, mm = mesh
    single = _put_scene(RenderEngine(cache=MPICache(quant=quant),
                                     max_bucket=8), scene)
    fleet = _put_scene(MeshRenderEngine(mesh_batch=mb, mesh_model=mm,
                                        cache=MPICache(quant=quant),
                                        max_bucket=8), scene)
    assert fleet.num_devices() == mb * mm
    rgb_s, depth_s = single.render("img", scene["poses"])
    rgb_m, depth_m = fleet.render("img", scene["poses"])
    np.testing.assert_array_equal(rgb_m, rgb_s)
    np.testing.assert_array_equal(depth_m, depth_s)


def test_mesh_render_bitwise_with_bucket_floor(scene):
    """P=1 pose floors to the mesh_batch=4 bucket on the fleet engine but
    only a 1-bucket on the single engine — different padding, identical
    real rows (per-pose independence)."""
    single = _put_scene(RenderEngine(cache=MPICache(quant="bf16"),
                                     max_bucket=8), scene)
    fleet = _put_scene(MeshRenderEngine(mesh_batch=4,
                                        cache=MPICache(quant="bf16"),
                                        max_bucket=8), scene)
    for j in range(3):
        rgb_s, depth_s = single.render("img", scene["poses"][j:j + 1])
        rgb_m, depth_m = fleet.render("img", scene["poses"][j:j + 1])
        np.testing.assert_array_equal(rgb_m, rgb_s)
        np.testing.assert_array_equal(depth_m, depth_s)


def test_mesh_render_many_entry_padding_bitwise(scene):
    """render_many across R=2 distinct entries (pads to bucket 2) through
    a 2x1 mesh: bitwise vs the single engine's coalesced call."""
    def build(cls, **kw):
        eng = _put_scene(cls(cache=MPICache(quant="bf16"), max_bucket=8,
                             **kw), scene)
        p2 = scene["planes"][::-1].copy()
        eng.put("img2", p2[:, 0:3], p2[:, 3:4], scene["disparity"],
                scene["K"])
        return eng

    reqs = [("img", scene["poses"][0]), ("img2", scene["poses"][1]),
            ("img", scene["poses"][2])]
    out_s = build(RenderEngine).render_many(reqs)
    out_m = build(MeshRenderEngine, mesh_batch=2).render_many(reqs)
    for (rgb_s, dep_s), (rgb_m, dep_m) in zip(out_s, out_m):
        np.testing.assert_array_equal(rgb_m, rgb_s)
        np.testing.assert_array_equal(dep_m, dep_s)


def test_mesh_model_axis_requires_divisible_planes(scene):
    """S=4 planes cannot shard over an 8-wide model axis — loud error, not
    a silent reshard."""
    fleet = _put_scene(MeshRenderEngine(mesh_batch=1, mesh_model=8,
                                        cache=MPICache(quant="bf16"),
                                        max_bucket=8), scene)
    with pytest.raises(ValueError, match="divide the model"):
        fleet.render("img", scene["poses"][:1])


# ---------------- sharded plane cache ----------------

def test_sharded_cache_owner_routing_and_counters():
    cache = ShardedPlaneCache(num_shards=4)
    iid = "40000000aa"  # owner = shard 1 at N=4
    assert cache.owner(iid) == 1
    assert cache.route(1, iid) == 1       # owner-local: no remote hop
    assert cache.remote_routes == 0
    assert cache.route(0, iid) == 1       # cross-shard hop
    assert cache.remote_routes == 1
    _put_rand(cache, iid, seed=1)
    assert cache.owner_encodes == 1
    assert len(cache.shards[1]) == 1      # placed owner-side
    assert sum(len(s) for i, s in enumerate(cache.shards) if i != 1) == 0
    assert iid in cache
    assert cache.get(iid) is not None
    assert cache.owner_hits == 1
    stats = cache.stats()
    assert stats["shards"] == 4 and stats["entries"] == 1
    assert len(stats["per_shard"]) == 4


def test_sharded_cache_budget_is_per_shard():
    """The fleet budget splits evenly: one hot shard evicts only its own
    entries, never another shard's residency."""
    probe = ShardedPlaneCache(num_shards=1)
    nbytes = _put_rand(probe, "00aa", seed=0).nbytes
    # room for 2 entries per shard across 2 shards
    cache = ShardedPlaneCache(num_shards=2, capacity_bytes=4 * nbytes + 2)
    assert cache.shards[0].capacity_bytes == 2 * nbytes + 1
    low = ["%08x" % k for k in (0x1000, 0x2000, 0x3000)]   # all shard 0
    hi = "ffff0000"                                        # shard 1
    _put_rand(cache, hi, seed=9)
    for i, iid in enumerate(low):
        _put_rand(cache, iid, seed=i)
    # shard 0 held only 2 of its 3 entries; shard 1 untouched
    assert len(cache.shards[0]) == 2
    assert low[0] not in cache and low[1] in cache and low[2] in cache
    assert hi in cache


def test_sharded_cache_rebalance_moves_exactly_changed_ranges():
    cache = ShardedPlaneCache(num_shards=4)
    ids = ["%08x" % (i << 28) for i in range(0, 16, 2)]  # spread over range
    for i, iid in enumerate(ids):
        _put_rand(cache, iid, seed=i)
    before = {iid: cache.owner(iid) for iid in ids}
    moved = cache.rebalance(2)
    after = {iid: cache.owner(iid) for iid in ids}
    assert cache.num_shards == 2
    assert moved == sum(before[i] != after[i] for i in ids)
    assert cache.rebalances == 1
    for iid in ids:  # every entry survives, on its new owner
        assert iid in cache
        assert iid in cache.shards[after[iid]]
    # a no-op rebalance (same shard count) moves nothing
    assert cache.rebalance(2) == 0


def test_sharded_cache_events_pass_strict_schema(tmp_path, monkeypatch):
    """serve.shard.place / serve.shard.rebalance land in the event stream
    and pass the strict mtpu-ev1 validator."""
    monkeypatch.delenv(tevents.ENV_VAR, raising=False)
    tevents.reset()
    path = str(tmp_path / "ev.jsonl")
    tevents.configure(path)
    try:
        cache = ShardedPlaneCache(num_shards=2)
        _put_rand(cache, "00000000aa", seed=0)
        cache.rebalance(4)
    finally:
        tevents.reset()
    assert tevents.validate_file(path) == []
    kinds = [e["kind"] for e in tevents.read_events(path)]
    assert "serve.shard.place" in kinds
    assert "serve.shard.rebalance" in kinds


# ---------------- continuous batcher ----------------

def test_continuous_batcher_ready_logic(scene):
    engine = _put_scene(RenderEngine(cache=MPICache(quant="bf16"),
                                     max_bucket=4), scene)
    b = ContinuousBatcher(engine, max_requests=2, max_wait_ms=50.0,
                          start=False)
    now = time.perf_counter()
    assert not b._ready(now)                      # empty queue
    b.submit("img", scene["poses"][0])
    assert not b._ready(time.perf_counter())      # deadline not reached
    assert b._ready(b._pending[0][3] + 0.051)     # oldest deadline expired
    b.submit("img", scene["poses"][1])
    assert b._ready(time.perf_counter())          # full bucket: immediate
    # immediate mode: max_wait_ms=0 dispatches any non-empty queue
    b0 = ContinuousBatcher(engine, max_requests=8, max_wait_ms=0.0,
                           start=False)
    b0.submit("img", scene["poses"][0])
    assert b0._ready(time.perf_counter())


def test_continuous_batcher_flush_trigger_counters(scene):
    from mine_tpu import telemetry

    engine = _put_scene(RenderEngine(cache=MPICache(quant="bf16"),
                                     max_bucket=4), scene)
    mark = telemetry.spans.record("test.mark", 0, 0)
    b = ContinuousBatcher(engine, max_requests=2, max_wait_ms=50.0,
                          start=False)
    futs = [b.submit("img", scene["poses"][j]) for j in range(2)]
    assert b.flush() == 2                          # full bucket
    b.submit("img", scene["poses"][2])
    assert b.flush() == 1                          # partial: deadline path
    flushes = [r for r in telemetry.spans.records("serve.batcher.flush")
               if r.span_id > mark]
    assert [(r.fields["n"], r.fields["cause"]) for r in flushes] == [
        (2, "full"), (1, "deadline")]
    for f in futs:
        rgb, depth = f.result(timeout=5)
        assert rgb.shape == (3, H, W) and depth.shape == (1, H, W)


def test_continuous_batcher_threaded_deadline_dispatch(scene):
    """Threaded smoke: a lone sub-bucket request must dispatch at its
    deadline without a second submit to wake the thread."""
    engine = _put_scene(RenderEngine(cache=MPICache(quant="bf16"),
                                     max_bucket=4), scene)
    b = ContinuousBatcher(engine, max_requests=4, max_wait_ms=20.0)
    try:
        fut = b.submit("img", scene["poses"][0])
        rgb, _ = fut.result(timeout=10)
        assert rgb.shape == (3, H, W)
    finally:
        b.close()


def test_continuous_batcher_close_joins_dispatch_thread(scene):
    """Regression: close() must actually JOIN the dispatch thread (bounded),
    not just flip the flag and hope — a still-running thread after close
    races teardown and leaks into the next test's engine."""
    engine = _put_scene(RenderEngine(cache=MPICache(quant="bf16"),
                                     max_bucket=4), scene)
    b = ContinuousBatcher(engine, max_requests=4, max_wait_ms=20.0)
    thread = b._thread
    assert thread is not None and thread.is_alive()
    fut = b.submit("img", scene["poses"][0])
    assert b.close() is True          # joined within the bounded timeout
    assert b._thread is None          # handle dropped once joined
    assert not thread.is_alive()
    # the in-flight request was drained, not abandoned
    rgb, _ = fut.result(timeout=5)
    assert rgb.shape == (3, H, W)
    assert b.close() is True          # idempotent


# ---------------- fleet ----------------

def test_serve_fleet_end_to_end(scene):
    """submit() through a 2-device mesh + 4-shard cache: every future
    resolves bitwise-identical to the single-device engine, the routing
    counters move, and rebalance keeps serving."""
    single = _put_scene(RenderEngine(cache=MPICache(quant="bf16"),
                                     max_bucket=8), scene)
    fleet = ServeFleet(mesh_batch=2, cache_shards=4, max_requests=4,
                       max_wait_ms=5.0, max_bucket=8)
    _put_scene(fleet.engine, scene)
    try:
        futs = [fleet.submit("img", scene["poses"][j % 5])
                for j in range(8)]
        for j, fut in enumerate(futs):
            rgb, depth = fut.result(timeout=30)
            ref_rgb, ref_depth = single.render("img",
                                               scene["poses"][j % 5][None])
            np.testing.assert_array_equal(rgb, ref_rgb[0])
            np.testing.assert_array_equal(depth, ref_depth[0])
        stats = fleet.stats()
        assert stats["mesh"] == "2x1" and stats["shards"] == 4
        assert stats["owner_encodes"] == 1   # the one _put_scene
        assert stats["owner_hits"] >= 1      # request-path lookups hit
        assert stats["flushes"] >= 1
        fleet.cache.rebalance(2)
        rgb, _ = fleet.render("img", scene["poses"][:2])
        np.testing.assert_array_equal(
            rgb, single.render("img", scene["poses"][:2])[0])
    finally:
        fleet.close()


def test_serve_fleet_from_config_and_scheduler_validation():
    cfg = serve_config_from_dict({
        "serve.mesh_batch": 2, "serve.mesh_model": 1,
        "serve.cache_shards": 2, "serve.scheduler": "micro",
        "serve.cache_bytes": 0, "serve.cache_quant": "int8",
        "serve.max_bucket": 4, "serve.max_requests": 4,
        "serve.max_wait_ms": 1.0})
    fleet = ServeFleet.from_config(cfg, start=False)
    assert fleet.num_devices() == 2
    assert fleet.cache.num_shards == 2 and fleet.cache.quant == "int8"
    from mine_tpu.serve.batcher import MicroBatcher
    assert type(fleet.batcher) is MicroBatcher
    with pytest.raises(ValueError, match="scheduler"):
        ServeFleet(scheduler="bogus")


def test_serve_config_rejects_bad_fleet_keys():
    for bad in ({"serve.mesh_batch": 3}, {"serve.mesh_model": 0},
                {"serve.cache_shards": 0}, {"serve.scheduler": "nope"},
                {"serve.warp_backend": "auto"}):
        with pytest.raises(ValueError):
            serve_config_from_dict(bad)
    cfg = serve_config_from_dict({})
    assert cfg.mesh_batch == 1 and cfg.mesh_model == 1
    assert cfg.cache_shards == 1 and cfg.scheduler == "continuous"
    assert cfg.warp_backend == "xla"
    guarded = serve_config_from_dict({"serve.warp_backend": "pallas_diff"})
    assert guarded.warp_backend == "pallas_diff"
