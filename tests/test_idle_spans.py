"""benchmark/idle_spans.py on hand-built neutral traces and ring records:
the clock join from the benchmark's spans to the program's, and the split of
each device's idle time instant by instant by the dispatching thread's span.
No profiler, no jit."""

import json
import os

import numpy as np
import pytest

from benchmark import harness, idle_spans, trace_reduce
from mine_tpu.telemetry.spans import SpanRecord

MS = 1e6
D = 7_000_000_123.0          # profiler instant = ring instant + D
R0 = 10**12                  # the ring's clock at the window's start (ns)
W0 = R0 + D


class Ring:
    """Records in the order the ring would hold them (by close)."""

    def __init__(self):
        self.recs = []
        # set-up, long before the window: the ring reaches back far enough
        self.add("setup", -5000, -4000)

    def add(self, name, t0_ms, t1_ms, thread="MainThread", parent=None,
            trace=None, **fields):
        sid = len(self.recs) + 1
        self.recs.append(SpanRecord(name, int(R0 + t0_ms * MS),
                                    int(R0 + t1_ms * MS), thread, sid,
                                    parent, trace, fields))
        return sid

    def records(self):
        return sorted(self.recs, key=lambda r: r.t1_ns)


def make_trace(window_ms, busy_ms, bench=()):
    """A neutral trace reduced: `busy_ms` a list of busy intervals per
    device (ring ms), `bench` (name, start_ns, end_ns) on the profiler's
    clock."""
    w0, w1 = W0, W0 + window_ms * MS
    host = [["bench.trace_window", w0, w1 - w0, {}]]
    host += [["bench." + n, s, e - s, {}] for n, s, e in bench]
    planes = [{"name": "/host:CPU",
               "lines": [{"name": "python", "events": host}]}]
    for d, busy in enumerate(busy_ms):
        ops = [["%%fusion.%d = f32[8]{0} fusion(%%p)" % i, W0 + s * MS,
                (e - s) * MS, {}] for i, (s, e) in enumerate(busy)]
        planes.append({"name": "/device:TPU:%d" % d,
                       "lines": [{"name": "XLA Ops", "events": ops}]})
    return trace_reduce.reduce({"planes": planes})


ONE_OP = [[(0.0, 0.5)]]   # a device plane needs an operation
CLOCK = idle_spans.Join(D, 0.0, 20, "step.dispatch")


def _shares(trace, ring, kind, clock=CLOCK):
    got = idle_spans.split(trace, ring.records(), idle_spans.KINDS[kind],
                           clock)
    assert got is not None
    return got


# ---------------- the clock join ----------------

def _nested_pairs(n=20, margin_us=(0.5, 2.0), extra=5, seed=0):
    """n benchmark dispatch spans inside the window, each holding one ring
    dispatch by a margin on each side; `extra` ring dispatches before and
    after the window. -> (ring, bench spans, window ms)"""
    rng = np.random.RandomState(seed)
    starts = np.cumsum(rng.uniform(100.0, 140.0, size=n + 2 * extra))
    starts -= starts[extra] - 1.0    # the first traced pair starts at 1 ms
    ring, bench = Ring(), []
    for i, t in enumerate(starts):
        dur = rng.uniform(5.0, 10.0)
        a, c = rng.uniform(*margin_us, size=2) * 1e-3   # us -> ms
        ring.add("train.step.dispatch", t + a, t + dur - c)
        if extra <= i < extra + n:
            bench.append(("step.dispatch", W0 + t * MS, W0 + (t + dur) * MS))
    return ring, bench, starts[extra + n - 1] + 20.0


def test_planted_offset_recovered_from_twenty_nested_pairs():
    ring, bench, window = _nested_pairs()
    trace = make_trace(window, ONE_OP, bench)
    got = idle_spans.join(trace, ring.records())
    assert got is not None and got.pairs == 20
    assert abs(got.offset_ns - D) < 1e3      # within 1 us
    assert got.width_ns <= 4e3


@pytest.mark.parametrize("fault", ["shifted", "few", "wide", "periodic"])
def test_join_refuses(fault):
    if fault == "periodic":
        # every step alike: more than one alignment fits
        ring, bench = Ring(), []
        for i in range(12):
            ring.add("train.step.dispatch", i * 100.0 + 0.001,
                     i * 100.0 + 5.0)
            if 3 <= i < 9:
                bench.append(("step.dispatch", W0 + i * 100.0 * MS,
                              W0 + (i * 100.0 + 5.002) * MS))
        trace = make_trace(1000.0, ONE_OP, bench)
        assert idle_spans.join(trace, ring.records()) is None
        return
    ring, bench, window = _nested_pairs(
        n=4 if fault == "few" else 20,
        margin_us=(60.0, 80.0) if fault == "wide" else (0.5, 2.0))
    recs = ring.records()
    if fault == "shifted":
        # one of the traced dispatches is missing from the ring
        dispatches = [r for r in recs if r.name == "train.step.dispatch"]
        recs = [r for r in recs if r is not dispatches[15]]
    trace = make_trace(window, ONE_OP, bench)
    assert idle_spans.join(trace, recs) is None


@pytest.mark.parametrize("takes", [0, 3])
def test_a_take_pins_what_the_dispatch_leaves_open(takes):
    """The train loop drops the old state inside `bench.step.dispatch` after
    the program's dispatch closes (~1.5 ms): the dispatches bound the offset
    from below only, and the loop's take of a batch inside
    `bench.feed.next` from above."""
    ring, bench, window = _nested_pairs(margin_us=(0.5, 2.0))
    bench = [(n, s, e + 0.8 * MS) for n, s, e in bench]
    for i in range(takes):
        b0, b1 = bench[3 + 5 * i][1], bench[3 + 5 * i][2]
        # a feed.next between this dispatch's end and the next one's start
        f0, f1 = b1 + 0.01 * MS, b1 + 20.0 * MS
        bench.append(("feed.next", f0, f1))
        ring.add("data.stage.take", (f0 - W0) / MS + 0.004,
                 (f1 - W0) / MS - 0.003 - i * 0.001)
    trace = make_trace(window, ONE_OP, bench)
    got = idle_spans.join(trace, ring.records())
    if not takes:
        assert got is None
        return
    assert got is not None and got.refined == 3
    assert abs(got.offset_ns - D) < 2e3 and got.width_ns < 5e3


@pytest.mark.parametrize("edge", ["start", "end"])
def test_a_take_in_a_feed_span_the_window_cuts_is_left_out(edge):
    """At an epoch's change the loop takes twice inside one
    `bench.feed.next`: the end of the old stager, then the new one's first
    batch. Where that span began before the window (or ends after it), a
    take that lies inside the window has no benchmark span inside it, and
    says nothing of the offset."""
    ring, bench, window = _nested_pairs(margin_us=(0.5, 2.0))
    bench = [(n, s, e + 0.8 * MS) for n, s, e in bench]
    for i in range(3):
        b1 = bench[3 + 5 * i][2]
        f0, f1 = b1 + 0.01 * MS, b1 + 20.0 * MS
        bench.append(("feed.next", f0, f1))
        ring.add("data.stage.take", (f0 - W0) / MS + 0.004,
                 (f1 - W0) / MS - 0.003)
    if edge == "start":    # the first traced dispatch starts at 1 ms
        bench.append(("feed.next", W0 - 1.75 * MS, W0 + 0.9 * MS))
        ring.add("data.stage.take", -1.74, -1.73)
        ring.add("data.stage.take", 0.2, 0.85)
    else:                  # the last one ends by 11 ms before the window's
        bench.append(("feed.next", W0 + (window - 5.0) * MS,
                      W0 + (window + 2.0) * MS))
        ring.add("data.stage.take", window - 4.0, window - 3.9)
    trace = make_trace(window, ONE_OP, bench)
    got = idle_spans.join(trace, ring.records())
    assert got is not None and got.refined == 3
    assert abs(got.offset_ns - D) < 2e3 and got.width_ns < 5e3


def test_a_take_that_fits_no_feed_span_refuses():
    ring, bench, window = _nested_pairs()
    ring.add("data.stage.take", 500.0, 501.0)   # no bench.feed.next
    trace = make_trace(window, ONE_OP, bench)
    assert idle_spans.join(trace, ring.records()) is None


def test_serve_anchor_is_the_enqueue_inside_the_submit():
    ring, bench = Ring(), []
    rng = np.random.RandomState(1)
    t = 0.0
    for i in range(30):
        t += rng.exponential(20.0)
        enq = t + rng.uniform(0.005, 0.02)
        flush = ring.add("serve.batcher.flush", enq + 3.0, enq + 9.0,
                         thread="mine-tpu-serve-batcher")
        ring.add("serve.batcher.queue_wait", enq, enq + 3.0,
                 thread="mine-tpu-serve-batcher", parent=flush)
        if 2 <= i < 28:
            bench.append(("serve.submit", W0 + t * MS, W0 + (t + 0.03) * MS))
    trace = make_trace(t + 50.0, ONE_OP, bench)
    got = idle_spans.join(trace, ring.records())
    assert got is not None and got.anchor == "serve.submit"
    assert got.pairs == 26 and abs(got.offset_ns - D) < 15e3


def test_spans_at_the_windows_edges_are_dropped():
    ring, bench, window = _nested_pairs()
    # a benchmark span the profiler cut at the window's start, with no
    # ring record of its own: kept, it would shift every pair
    bench = [("step.dispatch", W0 - 1.0 * MS, W0 + 2.0 * MS)] + bench
    trace = make_trace(window, ONE_OP, bench)
    got = idle_spans.join(trace, ring.records())
    assert got is not None and got.pairs == 20


# ---------------- the partition ----------------

def _train_scene(steps=20, devices=1):
    """A loop that dispatches a step every 50 ms; the device runs 6-30 ms
    after each start; at 30-45 ms the loop waits on the stager, which waits
    for a host batch until 35 and copies it until 44."""
    ring = Ring()
    busy = []
    for i in range(steps):
        b = i * 50.0 + (i * 7 % 5) * 0.1   # not quite periodic
        ring.add("train.step.dispatch", b, b + 5.0)
        ring.add("data.stage.host_wait", b + 28.0, b + 35.0,
                 thread="mine-tpu-prefetch")
        ring.add("data.stage.h2d", b + 35.0, b + 44.0,
                 thread="mine-tpu-prefetch")
        ring.add("data.stage.starved", b + 30.0, b + 45.0)
        busy.append((b + 6.0, b + 30.0))
    window = steps * 50.0
    return ring, [busy] * devices, window


def test_train_shares_sum_to_the_idle_share():
    ring, busy, window = _train_scene()
    trace = make_trace(window, busy)
    got = _shares(trace, ring, "train")
    shares = got["shares"]
    assert set(shares) == {"h2d", "host_batch", "launch", "unnamed"}
    assert abs(sum(shares.values()) - 100.0 * trace["idle_share"]) < 1e-9
    # each 50 ms: host batch 30-35, h2d 35-44, launch 0-5 of the next
    per = 50.0 * 20
    assert shares["host_batch"] == pytest.approx(100 * 5.0 * 20 / per)
    assert shares["h2d"] == pytest.approx(100 * 9.0 * 20 / per)
    assert shares["launch"] == pytest.approx(100 * 5.0 * 20 / per)
    assert got["table"][("host_batch", "data.stage.host_wait")] == \
        pytest.approx(100.0)


def test_serve_shares_sum_to_the_idle_share():
    ring = Ring()
    th = "mine-tpu-serve-batcher"
    busy = []
    for i in range(10):
        b = i * 100.0
        ring.add("serve.batcher.idle", b, b + 20.0, thread=th)
        ring.add("serve.batcher.linger", b + 20.0, b + 22.0, thread=th)
        fl = ring.add("serve.batcher.flush", b + 22.0, b + 80.0, thread=th)
        ring.add("serve.batcher.queue_wait", b + 5.0, b + 22.0, thread=th,
                 parent=fl)
        ring.add("serve.render.gather", b + 23.0, b + 25.0, thread=th,
                 parent=fl)
        call = ring.add("serve.render_call", b + 25.0, b + 75.0, thread=th,
                        parent=fl)
        ring.add("serve.render.pad_place", b + 25.0, b + 40.0, thread=th,
                 parent=call)
        dev = ring.add("serve.render.device", b + 40.0, b + 75.0, thread=th,
                       parent=call)
        ring.add("serve.render.dispatch", b + 41.0, b + 43.0, thread=th,
                 parent=dev)
        ring.add("serve.render.device_wait", b + 43.0, b + 70.0, thread=th,
                 parent=dev)
        ring.add("serve.render_fetch", b + 70.0, b + 75.0, thread=th,
                 parent=dev)
        ring.add("serve.batcher.deliver", b + 76.0, b + 79.0, thread=th,
                 parent=fl)
        busy.append((b + 44.0, b + 69.0))
    trace = make_trace(1000.0, [busy])
    got = _shares(trace, ring, "serve")
    shares = got["shares"]
    assert abs(sum(shares.values()) - 100.0 * trace["idle_share"]) < 1e-9
    # each 100 ms: sched 0-22; host 22-41 and 70-80; launch 41-44 and
    # 69-70; unnamed 80-100
    assert shares["sched"] == pytest.approx(22.0)
    assert shares["launch"] == pytest.approx(4.0)
    assert shares["host"] == pytest.approx(19.0 + 10.0)
    assert shares["unnamed"] == pytest.approx(20.0)
    table = got["table"]
    assert table[("host", "serve.render.pad_place")] == pytest.approx(150.0)
    assert table[("host", "serve.render.gather")] == pytest.approx(20.0)
    # the queue wait starts on the submitting thread: not on the stack
    assert not any(name == "serve.batcher.queue_wait" for _, name in table)


def test_a_gap_is_split_where_the_span_changes():
    ring = Ring()
    ring.add("data.stage.host_wait", -10.0, 0.0, thread="mine-tpu-prefetch")
    ring.add("data.stage.h2d", 0.0, 100.0, thread="mine-tpu-prefetch")
    ring.add("data.stage.starved", 0.0, 40.0)
    ring.add("train.step.dispatch", 40.0, 55.0)
    trace = make_trace(100.0, [[(60.0, 100.0)]])
    got = _shares(trace, ring, "train")["shares"]
    assert got["h2d"] == pytest.approx(40.0)
    assert got["launch"] == pytest.approx(15.0)
    assert got["unnamed"] == pytest.approx(5.0)
    assert got["host_batch"] == 0.0


def test_a_span_on_another_thread_does_not_name_the_gap():
    ring = Ring()
    ring.add("train.step.dispatch", 0.0, 5.0)
    ring.add("train.step.dispatch", 90.0, 95.0)
    # another thread waits on a queue and dispatches; the loop does neither
    ring.add("data.stage.starved", 10.0, 50.0, thread="elsewhere")
    ring.add("train.step.dispatch", 50.0, 60.0, thread="elsewhere")
    ring.add("data.stage.host_wait", -10.0, 10.0, thread="mine-tpu-prefetch")
    ring.add("data.stage.h2d", 10.0, 60.0, thread="mine-tpu-prefetch")
    trace = make_trace(100.0, [[(0.0, 5.0), (60.0, 90.0)]])
    got = _shares(trace, ring, "train")
    assert got["thread"] == "MainThread"
    shares = got["shares"]
    assert shares["unnamed"] == pytest.approx(55.0 + 5.0)
    assert shares["launch"] == pytest.approx(5.0)
    assert shares["h2d"] == 0.0 and shares["host_batch"] == 0.0


def test_four_devices_are_averaged():
    ring = Ring()
    ring.add("train.step.dispatch", 0.0, 20.0)
    ring.add("data.stage.starved", 20.0, 100.0)
    ring.add("data.stage.host_wait", 0.0, 10.0, thread="mine-tpu-prefetch")
    ring.add("data.stage.h2d", 10.0, 100.0, thread="mine-tpu-prefetch")
    # device d idles from 10 d ms to 40 ms
    busy = [[(0.0, 10.0 * d), (40.0, 100.0)] for d in range(4)]
    trace = make_trace(100.0, busy)
    got = _shares(trace, ring, "train")["shares"]
    # launch: [10 d, 20) for d = 0, 1 -> 20, 10, 0, 0; h2d: [max(20, 10 d), 40)
    assert got["launch"] == pytest.approx((20 + 10 + 0 + 0) / 4)
    assert got["h2d"] == pytest.approx((20 + 20 + 20 + 10) / 4)
    assert abs(sum(got.values()) - 100.0 * trace["idle_share"]) < 1e-9


def test_reader_reads_once_and_a_program_without_the_span_reports_none(
        monkeypatch):
    ring, busy, window = _train_scene()
    # a program whose stager does not record its wait for a host batch
    recs = [r for r in ring.records() if r.name != "data.stage.host_wait"]
    bench = [("step.dispatch", W0 + r.t0_ns - R0 - 0.001 * MS,
              W0 + r.t1_ns - R0 + 0.001 * MS)
             for r in recs if r.name == "train.step.dispatch"
             and r.t0_ns > R0]
    trace = make_trace(window, busy, bench)
    calls = []
    monkeypatch.setattr(idle_spans, "_ring",
                        lambda: calls.append(1) or recs)
    obs = {"trace": trace}
    assert idle_spans.share(obs, "train", "host_batch") is None
    h2d = idle_spans.share(obs, "train", "h2d")
    unnamed = idle_spans.share(obs, "train", "unnamed")
    launch = idle_spans.share(obs, "train", "launch")
    assert len(calls) == 1
    assert h2d + unnamed + launch == pytest.approx(
        100.0 * trace["idle_share"], abs=1e-9)
    # no trace, no split
    assert idle_spans.share({"trace": None}, "serve", "host") is None


READERS = {"idle_h2d_share.train": ("train", "h2d"),
           "idle_host_batch_share.train": ("train", "host_batch"),
           "idle_launch_share.train": ("train", "launch"),
           "idle_unnamed_share.train": ("train", "unnamed"),
           "idle_sched_share.serve": ("serve", "sched"),
           "idle_host_share.serve": ("serve", "host"),
           "idle_launch_share.serve": ("serve", "launch"),
           "idle_unnamed_share.serve": ("serve", "unnamed")}


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_reader_reads_its_share_as_the_manifest_lists_it(
        name, monkeypatch):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    reader = harness.load_module(os.path.join(
        root, "benchmark", "layer_metrics", name + ".py"), name)
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    kind, share = READERS[name]
    shares = {s: float(i) for i, s in enumerate(
        idle_spans.KINDS[kind].shares)}
    monkeypatch.setattr(idle_spans, "_ring", lambda: ["a record"])
    monkeypatch.setattr(idle_spans, "split", lambda *a: {
        "shares": shares, "join": CLOCK, "thread": "t", "table": {}})
    obs = {"trace": {"window_s": 3.0, "devices": [{}]}}
    assert reader.read(obs) == shares[share]
