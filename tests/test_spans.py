"""The one span primitive (telemetry/spans.py) and where it is placed.

  * the record: fields, parent and thread under nesting and across a thread
    hand-off; pre-measured records; riders; the ring's export;
  * a span opened on a second thread shows in a CPU `jax.profiler` trace as
    `mine.<name>` with its `span_id` stat and the ring's duration;
  * the batcher: `idle + linger + flush` cover its thread's time, each
    request has one `queue_wait` record whose parent is its flush;
  * the engine: the call's spans nest as documented, a cold bucket stays out
    of `serve.render_call_ms`, and the render is bitwise what the jitted
    program gives without any span round it;
  * the feed: `data.*` spans of one short epoch;
  * the train step: `train.step.dispatch`, the lazily lowered op map
    (telemetry/programs.py), scopes that add metadata and nothing else.
"""

import glob
import os
import threading
import time

import numpy as np
import pytest

from mine_tpu import telemetry
from mine_tpu.telemetry import events as tevents
from mine_tpu.telemetry import programs, spans, tracing


@pytest.fixture
def clean_sink(monkeypatch):
    monkeypatch.delenv(tevents.ENV_VAR, raising=False)
    tevents.reset()
    yield
    tevents.reset()


def _since(mark):
    """Records filed after `mark` (a span id), oldest first."""
    return [r for r in spans.records() if r.span_id > mark]


def _mark():
    return spans.record("test.mark", 0, 0)


# ---------------- the record ----------------

def test_record_fields_nesting_and_thread():
    mark = _mark()
    with telemetry.span("test.outer", step=3) as outer:
        with telemetry.span("test.inner") as inner:
            time.sleep(0.002)
    recs = {r.name: r for r in _since(mark)}
    o, i = recs["test.outer"], recs["test.inner"]
    assert isinstance(o, telemetry.SpanRecord)
    assert o._fields == ("name", "t0_ns", "t1_ns", "thread", "span_id",
                         "parent", "trace", "fields")
    assert o.parent is None and i.parent == o.span_id == outer.span_id
    assert i.span_id == inner.span_id and i.span_id > o.span_id
    assert o.thread == i.thread == threading.current_thread().name
    assert o.t0_ns <= i.t0_ns < i.t1_ns <= o.t1_ns
    assert i.ms >= 2.0 and inner.ms == i.ms
    assert o.fields == {"step": 3} and o.trace is None
    assert telemetry.histogram("test.inner_ms").count >= 1


def test_parent_passed_across_a_thread_handoff():
    """A span on another thread names the span that caused it; without the
    hand-off a thread's first span has no parent (stacks are per thread)."""
    mark = _mark()
    with telemetry.span("test.submit") as submit:
        def work():
            with telemetry.span("test.caused", parent=submit.span_id):
                pass
            with telemetry.span("test.orphan"):
                pass
        t = threading.Thread(target=work, name="span-handoff")
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    recs = {r.name: r for r in _since(mark)}
    assert recs["test.caused"].parent == submit.span_id
    assert recs["test.caused"].thread == "span-handoff"
    assert recs["test.orphan"].parent is None
    assert recs["test.submit"].thread != "span-handoff"


def test_premeasured_record_and_default_parent():
    mark = _mark()
    with telemetry.span("test.flush") as flush:
        sid = spans.record("test.queued", 1_000, 4_001_000, cause="full")
    explicit = spans.record("test.queued", 5, 6, parent=flush.span_id)
    recs = [r for r in _since(mark) if r.name == "test.queued"]
    assert [r.span_id for r in recs] == [sid, explicit]
    assert recs[0].parent == flush.span_id == recs[1].parent
    assert recs[0].ms == 4.0 and recs[0].fields == {"cause": "full"}


def test_riders_and_trace_children_share_the_record(clean_sink):
    """A span forwards itself to the TraceContexts riding on it; a trace's
    own child is the same record with `trace` set, and keeps its
    trace.span event (tests/test_tracing.py sees what it saw)."""
    tracing.reset()
    a = tracing.start("serve.request", sample=1.0)
    b = tracing.start("serve.request", sample=1.0)
    mark = _mark()
    with telemetry.span("test.pad_place", riders=(a, None, b),
                        rider_name="pad", poses_bucket=4) as pad:
        pass
    with a.child("route", owner_shard=1):
        pass
    a.add_span("queue", 3.25, flush_cause="deadline")
    recs = _since(mark)
    own = [r for r in recs if r.name == "test.pad_place"]
    assert len(own) == 1 and own[0].trace is None
    ridden = [r for r in recs if r.name == "pad"]
    assert sorted(r.trace for r in ridden) == sorted([a.trace_id, b.trace_id])
    assert all(r.parent == pad.span_id and r.fields["poses_bucket"] == 4
               and (r.t0_ns, r.t1_ns) == (pad.t0_ns, pad.t1_ns)
               for r in ridden)
    by_name = {r.name: r for r in recs if r.trace == a.trace_id}
    assert set(by_name) == {"pad", "route", "queue"}
    assert by_name["queue"].ms == 3.25
    assert by_name["route"].fields == {"owner_shard": 1}
    tracing.finish(a)
    tracing.finish(b)
    names = [s["name"] for s in tracing.recent()[1]["spans"]]
    assert names == ["serve.request", "pad", "route", "queue"]
    tracing.reset()


def test_export_writes_the_ring_as_span_events(tmp_path, clean_sink):
    spans.reset()
    path = str(tmp_path / "ev.jsonl")
    tevents.configure(path)
    with telemetry.span("test.live", emit=True):
        pass
    with telemetry.span("test.quiet", n=2) as quiet:
        pass
    ctx = tracing.start("r", sample=1.0)
    ctx.add_span("queue", 1.0)
    assert spans.export() == 1   # the live one and the trace's are there
    tevents.current_sink().close()
    assert tevents.validate_file(path, strict_kinds=True) == []
    events = [e for e in tevents.read_events(path) if e["kind"] == "span"]
    assert [e["name"] for e in events] == ["test.live", "test.quiet"]
    assert events[1]["span_id"] == quiet.span_id and events[1]["n"] == 2
    assert events[1]["t1_ns"] - events[1]["t0_ns"] == quiet.t1_ns - quiet.t0_ns
    tracing.reset()


def test_ring_is_bounded():
    assert spans._ring.maxlen == spans.RING_CAPACITY == 65536


def test_host_readback_is_a_span():
    mark = _mark()
    before = telemetry.readback_counts().get("test.fetch", 0)
    with telemetry.host_readback("test.fetch"):
        pass
    assert [r.name for r in _since(mark)] == ["test.fetch"]
    assert telemetry.readback_counts()["test.fetch"] == before + 1


# ---------------- on the profiler's clock ----------------

def _profiled_span(trace_dir, out):
    import jax
    from jax.profiler import ProfileData
    jax.numpy.zeros(1).block_until_ready()
    jax.profiler.start_trace(trace_dir)
    try:
        def work():
            with telemetry.span("test.profiled", batch=5) as sp:
                time.sleep(0.02)
            out["span"] = sp
        t = threading.Thread(target=work, name="span-second-thread")
        t.start()
        t.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    found = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "mine.test.profiled":
                    found.append((dict(ev.stats), ev.duration_ns))
    out["events"] = found


def test_span_shows_in_a_profiler_trace(tmp_path):
    """Its own time limit: the profiler runs in a thread that is given 180
    seconds (no pytest-timeout here)."""
    out = {}
    worker = threading.Thread(target=_profiled_span,
                              args=(str(tmp_path / "trace"), out),
                              name="span-profile", daemon=True)
    worker.start()
    worker.join(timeout=180)
    assert not worker.is_alive(), "the CPU profiler did not finish in 180 s"
    sp = out["span"]
    assert len(out["events"]) == 1, out["events"]
    stats, duration_ns = out["events"][0]
    assert stats["span_id"] == sp.span_id
    assert abs(duration_ns - (sp.t1_ns - sp.t0_ns)) < 1_000_000
    rec = spans.records("test.profiled")[-1]
    assert rec.span_id == sp.span_id and rec.thread == "span-second-thread"


# ---------------- the batcher ----------------

class _StubEngine:
    """render_many that takes 20 ms and renders nothing."""

    def __init__(self):
        self.calls = []

    def render_many(self, requests, traces=None, images=None, degraded=None):
        time.sleep(0.02)
        self.calls.append(len(requests))
        return [(np.zeros((3, 1, 1), np.float32),
                 np.zeros((1, 1, 1), np.float32)) for _ in requests]


POSE = np.eye(4, dtype=np.float32)


def test_each_request_has_one_queue_wait_whose_parent_is_its_flush():
    from mine_tpu.serve.batcher import ContinuousBatcher
    b = ContinuousBatcher(_StubEngine(), max_requests=4, max_wait_ms=5.0,
                          start=False, auto_trace=False)
    clock = iter(100.0 + 0.001 * k for k in range(100))
    b._now = lambda: next(clock)   # the injectable clock: enqueue instants
    mark = _mark()
    waits0 = telemetry.histogram("serve.batcher.queue_wait_ms").count
    futs = [b.submit("img%d" % k, POSE) for k in range(6)]
    assert b.flush() == 4 and b.flush() == 2 and b.flush() == 0
    assert all(f.done() for f in futs)
    recs = _since(mark)
    flushes = [r for r in recs if r.name == "serve.batcher.flush"]
    assert [(r.fields["n"], r.fields["bucket"], r.fields["cause"],
             r.fields["seq"]) for r in flushes] == [
        (4, 4, "full", 0), (2, 2, "deadline", 1)]   # an empty flush: none
    waits = [r for r in recs if r.name == "serve.batcher.queue_wait"]
    assert len(waits) == 6
    assert [w.parent for w in waits] == [flushes[0].span_id] * 4 + [
        flushes[1].span_id] * 2
    # enqueue instants are the injected clock's, to the nanosecond
    assert [w.t0_ns for w in waits] == [
        int((100.0 + 0.001 * k) * 1e9) for k in range(6)]
    assert telemetry.histogram(
        "serve.batcher.queue_wait_ms").count == waits0 + 6
    delivers = [r for r in recs if r.name == "serve.batcher.deliver"]
    assert [d.parent for d in delivers] == [f.span_id for f in flushes]
    b.close()


def test_idle_linger_flush_cover_the_batcher_threads_time():
    from mine_tpu.serve.batcher import ContinuousBatcher
    engine = _StubEngine()
    b = ContinuousBatcher(engine, max_requests=4, max_wait_ms=15.0,
                          start=False, auto_trace=False)
    thread = threading.Thread(target=b._run, name="test-batcher-thread",
                              daemon=True)
    mark = _mark()
    thread.start()
    futs = []
    for burst in (1, 4, 2, 5):    # lingering, full and mixed flushes
        futs += [b.submit("img", POSE) for _ in range(burst)]
        time.sleep(0.05)
    for f in futs:
        f.result(timeout=30)
    with b._cv:
        b._closed = True
        b._cv.notify_all()
    thread.join(timeout=30)
    assert not thread.is_alive()
    mine = [r for r in _since(mark) if r.thread == "test-batcher-thread"]
    top = sorted((r for r in mine if r.parent is None),
                 key=lambda r: r.t0_ns)
    assert {r.name for r in top} == {"serve.batcher.idle",
                                     "serve.batcher.linger",
                                     "serve.batcher.flush"}
    for a, nxt in zip(top, top[1:]):
        assert a.t1_ns <= nxt.t0_ns   # one thread: no two at once
    covered = sum(r.t1_ns - r.t0_ns for r in top)
    lifetime = top[-1].t1_ns - top[0].t0_ns
    assert covered >= 0.9 * lifetime, (covered, lifetime)
    # everything else on the thread hangs under a flush
    flush_ids = {r.span_id for r in top if r.name == "serve.batcher.flush"}
    assert all(r.parent in flush_ids for r in mine if r.parent is not None)
    assert sum(engine.calls) == 12
    assert len([r for r in mine
                if r.name == "serve.batcher.queue_wait"]) == 12


# ---------------- the engine ----------------

@pytest.fixture(scope="module")
def small_engine():
    from mine_tpu.serve import MPICache, RenderEngine
    rng = np.random.RandomState(0)
    planes = rng.uniform(0.0, 1.0, (4, 4, 16, 16)).astype(np.float32)
    disparity = np.linspace(1.0, 0.1, 4).astype(np.float32)
    K = np.array([[20.0, 0, 8], [0, 20.0, 8], [0, 0, 1]], np.float32)
    engine = RenderEngine(cache=MPICache(quant="bf16"))
    engine.put("img", planes[:, 0:3], planes[:, 3:4], disparity, K)
    poses = np.tile(np.eye(4, dtype=np.float32), (3, 1, 1))
    poses[:, 0, 3] = [0.0, 0.01, 0.02]
    return engine, poses


def test_engine_call_spans_nest_and_cold_bucket_stays_out(small_engine):
    engine, poses = small_engine
    hist = telemetry.histogram("serve.render_call_ms")
    compiles0 = telemetry.counter("serve.bucket_compiles").value
    warm0, mark = hist.count, _mark()
    engine.render_many([("img", poses[0]), ("img", poses[1])])   # cold
    assert hist.count == warm0   # a serve.bucket_compile event instead
    assert telemetry.counter("serve.bucket_compiles").value == compiles0 + 1
    cold = {r.name: r for r in _since(mark)}
    assert cold["serve.render_call"].fields["compiled"] is True
    mark = _mark()
    engine.render_many([("img", poses[0]), ("img", poses[2])])   # warm
    assert hist.count == warm0 + 1
    recs = {r.name: r for r in _since(mark)}
    assert set(recs) == {
        "serve.render.gather", "serve.render_call", "serve.render.pad_place",
        "serve.render.device", "serve.render.dispatch",
        "serve.render.device_wait", "serve.render_fetch"}
    call, device = recs["serve.render_call"], recs["serve.render.device"]
    assert call.fields["compiled"] is False and call.fields["poses"] == 2
    assert recs["serve.render.gather"].parent is None
    assert recs["serve.render.pad_place"].parent == call.span_id
    assert device.parent == call.span_id
    for name in ("serve.render.dispatch", "serve.render.device_wait",
                 "serve.render_fetch"):
        assert recs[name].parent == device.span_id
    order = [recs[n] for n in (
        "serve.render.pad_place", "serve.render.dispatch",
        "serve.render.device_wait", "serve.render_fetch")]
    for a, nxt in zip(order, order[1:]):
        assert a.t1_ns <= nxt.t0_ns
    parts = sum(r.t1_ns - r.t0_ns for r in order)
    assert parts <= call.t1_ns - call.t0_ns
    assert abs(hist.snapshot()["max"] - call.ms) < 1e-6 or hist.count > 1


def test_render_bitwise_equals_the_program_without_spans(small_engine):
    """The spans (and the block_until_ready between dispatch and fetch)
    change nothing: `render` gives bit for bit what the jitted program
    gives when called with no span round it."""
    engine, poses = small_engine
    rgb, depth = engine.render("img", poses)
    entry = engine.cache.get("img")
    args = engine._stack_pad_place([entry], np.zeros(3, np.int32), poses,
                                   1, 4)
    ref_rgb, ref_depth = engine._render(*args, engine.warp_impl)
    np.testing.assert_array_equal(rgb, np.asarray(ref_rgb[:3]))
    np.testing.assert_array_equal(depth, np.asarray(ref_depth[:3]))


# ---------------- the feed ----------------

def test_feed_spans_of_one_epoch():
    from mine_tpu.data.pipeline import DeviceStager, threaded_pair_batches

    def get_pair(index, rng=None):
        time.sleep(0.002)
        item = {"img": np.zeros((4, 4, 3), np.float32),
                "K": np.eye(3, dtype=np.float32),
                "xyzs": np.zeros((3, 2), np.float32)}
        return item, dict(item, G_src_tgt=np.eye(4, dtype=np.float32))

    mark = _mark()
    host = threaded_pair_batches(8, get_pair, batch_size=2, shuffle=False,
                                 workers=2, prefetch_batches=2)
    staged = list(DeviceStager(host, lambda b: b, depth=2))
    assert len(staged) == 4
    recs = _since(mark)
    by = lambda n: [r for r in recs if r.name == n]   # noqa: E731
    (opened,) = by("data.iterator.open")
    assert opened.fields["workers"] == 2 and opened.ms >= 4.0
    assert opened.thread.startswith("mine-tpu-prefetch")
    built = by("data.assemble.batch")
    assert sorted(r.fields["batch"] for r in built) == [0, 1, 2, 3]
    assert all(r.thread.startswith("mine-tpu-assembler") for r in built)
    h2d = by("data.stage.h2d")
    assert len(h2d) == 4
    assert [s.h2d_ms for s in staged] == [r.ms for r in h2d]
    # every copy follows the stager's wait for its host batch, on the
    # stager's thread (one more wait meets the epoch's end); the epoch's
    # open happens inside the first wait
    host_wait = by("data.stage.host_wait")
    stager = sorted(h2d + host_wait, key=lambda r: r.t0_ns)
    assert {r.thread for r in stager} == {opened.thread}
    assert [r.name for r in stager] == [
        "data.stage.host_wait", "data.stage.h2d"] * 4 + [
        "data.stage.host_wait"]
    assert opened.parent == host_wait[0].span_id
    starved = by("data.stage.starved")
    # the consumer met an empty queue at least at the epoch's edge, on its
    # own thread, and waited there for the first batch to be built
    assert starved and starved[0].thread == threading.current_thread().name
    assert starved[0].t1_ns >= opened.t1_ns
    # each take of a batch (and of the epoch's end) is a span on the
    # consumer's thread; a starved wait lies inside its take
    takes = by("data.stage.take")
    assert len(takes) == 5
    assert {r.thread for r in takes} == {threading.current_thread().name}
    ids = {r.span_id for r in takes}
    assert all(r.parent in ids for r in starved)
    assert not by("data.host.starved")   # nobody named a stage "host" here


def test_epoch_open_carries_ready_and_is_short_when_ready():
    """`data.iterator.open` carries `ready`: 0 on a cold open, and on the
    open that finds the lookahead's batches it closes before building the
    first batch would have taken."""
    from mine_tpu.data.pipeline import threaded_pair_batches

    def get_pair(index, rng=None):
        time.sleep(0.005)
        item = {"img": np.zeros((4, 4, 3), np.float32),
                "K": np.eye(3, dtype=np.float32),
                "xyzs": np.zeros((3, 2), np.float32)}
        return item, dict(item, G_src_tgt=np.eye(4, dtype=np.float32))

    mark = _mark()
    for epoch in range(3):
        list(threaded_pair_batches(12, get_pair, batch_size=3, shuffle=True,
                                   epoch=epoch, workers=2))
        time.sleep(0.1)   # the loop's edge: the lookahead lands meanwhile
    recs = _since(mark)
    opens = [r for r in recs if r.name == "data.iterator.open"]
    assert [r.fields["epoch"] for r in opens] == [0, 1, 2]
    assert opens[0].fields["ready"] == 0 and opens[1].fields["ready"] == 0
    assert opens[2].fields["ready"] > 0
    (first,) = [r for r in recs if r.name == "data.assemble.batch"
                and r.fields["epoch"] == 2 and r.fields["batch"] == 0]
    assert opens[2].ms < first.ms


# ---------------- the train step, its op map, its scopes ----------------

def test_layer_of_name_paths():
    layer_of = programs.layer_of
    assert layer_of("jit(_train_step_impl)/jit(main)/transpose(jvp(decoder))"
                    "/ConvBlock_0/conv_general_dilated") == "decoder"
    assert layer_of("jit(f)/jvp(loss_pyramid)/render/render_src_s2/"
                    "mul") == "render"
    assert layer_of("jit(f)/transpose(jvp(loss_pyramid))/render/"
                    "warp_composite_tgt_s1/cond/branch_1_fun/"
                    "jit(_warp_bwd)/warp_bilinear_sample_bwd/"
                    "pallas_call") == "render"
    assert layer_of("jit(f)/jvp(loss_pyramid)/ssim_pairs_s0/"
                    "dot_general") == "loss_pyramid"
    assert layer_of("jit(f)/jvp(loss_pyramid)/reduce_sum") == "loss_pyramid"
    assert layer_of("jit(f)/jvp(MPIPredictor)/encoder/backbone/"
                    "conv") == "encoder"
    assert layer_of("jit(f)/adam_update/mul") == "optimizer"
    assert layer_of("jit(f)/nonfinite_guard/select_n") == "optimizer"
    assert layer_of("jit(f)/jit(main)/add_any") is None
    assert layer_of("reduce_window_sum") is None
    assert layer_of("") is None
    assert set(layer for _, layer in programs.SCOPE_LAYERS) == set(
        programs.LAYERS)


def test_programs_layers_on_a_two_scope_toy_jit():
    import jax
    import jax.numpy as jnp

    def loss(w, x):
        with jax.named_scope("decoder"):
            h = jnp.tanh(x @ w)
        return jnp.sum(h * h)

    def step(w, x):
        g = jax.grad(loss)(w, x)
        with jax.named_scope("adam_update"):
            return w - 0.1 * g

    f = jax.jit(step)
    w, x = jnp.ones((16, 16)), jnp.ones((4, 16))
    asked = []

    def text_fn():
        asked.append(1)
        return f.lower(w, x).compile().as_text()

    programs.reset()
    try:
        assert programs.layers("toy_step") is None
        assert not programs.registered("toy_step")
        programs.register("toy_step", text_fn)
        assert programs.registered("toy_step") and asked == []   # lazy
        found = programs.layers("toy_step")
        assert programs.layers("toy_step") is found and asked == [1]
    finally:
        programs.reset()
    assert set(found.values()) == {"decoder", "optimizer"}
    assert any("dot" in name for name, layer in found.items()
               if layer == "decoder")


def test_scopes_add_metadata_only():
    """`render` and `loss_pyramid` are decorators round whole functions:
    the lowered program is letter for letter the undecorated one's."""
    import jax
    import jax.numpy as jnp
    from mine_tpu.config import mpi_config_from_dict
    from mine_tpu.data.synthetic import make_batch
    from mine_tpu.train import loss as loss_mod
    from tests.test_train import tiny_config

    cfg = mpi_config_from_dict(tiny_config())
    batch = {k: jnp.asarray(v)
             for k, v in make_batch(1, 64, 64, num_points=16).items()}
    mpis = [jnp.full((1, 4, 4, 64 >> s, 64 >> s), 0.5) for s in range(4)]
    disparity = jnp.linspace(1.0, 0.2, 4)[None]

    def lowered(fn):
        return jax.jit(lambda m, d, b: fn(m, d, b, cfg)[0]).lower(
            mpis, disparity, batch)

    scoped = lowered(loss_mod.compute_losses)
    plain = lowered(loss_mod.compute_losses.__wrapped__)
    assert scoped.as_text() == plain.as_text()
    assert "loss_pyramid" in scoped.as_text(debug_info=True)
    assert "loss_pyramid/render" in scoped.as_text(debug_info=True)


def test_train_step_dispatch_span_and_lazy_program_map():
    """One tiny train step: the public `train_step` is the jitted program
    plus a span (bitwise), it registers the op map without lowering
    anything, and the map names all five layers when asked."""
    import jax
    import jax.numpy as jnp
    from mine_tpu.data.synthetic import make_batch
    from mine_tpu.train.step import SynthesisTrainer
    from tests.test_train import tiny_config

    programs.reset()
    trainer = SynthesisTrainer(tiny_config(), steps_per_epoch=10)
    state = trainer.init_state(batch_size=1)
    batch = {k: jnp.asarray(v)
             for k, v in make_batch(1, 64, 64, num_points=16).items()}
    copy = jax.tree_util.tree_map(jnp.copy, state)   # the step donates
    assert not programs.registered("_train_step_impl")
    mark = _mark()
    new_state, metrics = trainer.train_step(state, batch)
    assert [r.name for r in _since(mark)] == ["train.step.dispatch"]
    assert programs.registered("_train_step_impl")
    assert "_train_step_impl" not in programs._maps   # nothing lowered yet
    ref_state, ref_metrics = trainer._train_step(copy, batch)   # no span
    for a, b in zip(jax.tree_util.tree_leaves((new_state, metrics)),
                    jax.tree_util.tree_leaves((ref_state, ref_metrics))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # asking for the map lowers from the remembered avals and finds the
    # executable the step runs: no second compile (chip run, PR 28: an aval
    # that commits an uncommitted argument compiled again, 116 s)
    import logging
    compiles = []

    class Watch(logging.Handler):
        def emit(self, record):
            compiles.append(record.getMessage())

    logger = logging.getLogger("jax._src.compiler")
    watch, level = Watch(level=logging.DEBUG), logger.level
    logger.addHandler(watch)
    logger.setLevel(logging.DEBUG)
    try:
        found = programs.layers("_train_step_impl")
    finally:
        logger.removeHandler(watch)
        logger.setLevel(level)
        programs.reset()
    assert not [m for m in compiles if "_train_step_impl" in m], compiles
    assert set(found.values()) == set(programs.FAMILY_LAYERS["mine"])
