"""The readers of the program's own spans (PR 28), each on a hand-built
`obs`; the CPU rehearsal prints them; a program without spans reports none."""

import collections
import json

import pytest

from benchmark import harness, program_spans, run

SERVE_METRICS = ("batcher_wait_share.serve", "host_busy_share.serve",
                 "pad_place_ms.serve", "dispatch_wait_ms.serve",
                 "readback_ms.serve", "batcher_unaccounted_share.serve")
FEED_METRICS = ("feed_starved_ms.train", "h2d_ms.train",
                "host_batch_ms.train", "epoch_open_ms.train")
STEP_METRICS = ("step_dispatch_ms.train", "step_gap_max_ms.train")
LAYER_METRICS = ("encoder_ms.train", "decoder_ms.train", "render_ms.train",
                 "loss_pyramid_ms.train", "optimizer_ms.train",
                 "scope_unattributed.train")


def _reader(name):
    return harness.Cell("llff_serve_steady").layer_reader(name)


def _hist(count, total):
    return {"count": count, "sum": total}


def _serve_obs():
    """A 10 s window of 50 calls: the thread idles 1.0 s, lingers 1.5 s and
    flushes for 7.3 s, of which 0.3 s dispatching and 5.0 s waiting on the
    device; before the window every histogram already held 10 records."""
    per_call = {"serve.batcher.idle_ms": 20.0, "serve.batcher.linger_ms": 30.0,
                "serve.batcher.flush_ms": 146.0,
                "serve.render.pad_place_ms": 12.0,
                "serve.render.dispatch_ms": 6.0,
                "serve.render.device_wait_ms": 100.0,
                "serve.render_fetch_ms": 16.0}
    start = {k: _hist(10, 10 * 999.0) for k in per_call}
    end = {k: _hist(60, 10 * 999.0 + 50 * v) for k, v in per_call.items()}
    return {"registry": {"start": start, "end": end}, "window_s": 10.0}


def test_serve_readers_by_hand():
    obs = _serve_obs()
    read = {m: _reader(m).read(obs) for m in SERVE_METRICS}
    assert read["batcher_wait_share.serve"] == pytest.approx(25.0)
    # (7.3 - 0.3 - 5.0) s of the flushes were the host's
    assert read["host_busy_share.serve"] == pytest.approx(20.0)
    assert read["pad_place_ms.serve"] == pytest.approx(12.0)
    assert read["dispatch_wait_ms.serve"] == pytest.approx(106.0)
    assert read["readback_ms.serve"] == pytest.approx(16.0)
    # 1.0 + 1.5 + 7.3 s of 10 s are covered
    assert read["batcher_unaccounted_share.serve"] == pytest.approx(2.0)


def test_serve_readers_find_nothing_in_an_older_program():
    """The parent of PR 28 has `serve.render_call_ms` and no span
    histogram: every reader returns None and none raises."""
    old = {"serve.render_call_ms": _hist(50, 5000.0), "serve.sync_encode": 0}
    obs = {"registry": {"start": {}, "end": old}, "window_s": 10.0}
    assert [_reader(m).read(obs) for m in SERVE_METRICS] == [None] * 6
    assert [_reader(m).read({"registry": {}, "window_s": 10.0})
            for m in SERVE_METRICS] == [None] * 6


Rec = collections.namedtuple("Rec", "name t0_ns t1_ns")


def _ring(monkeypatch, recs):
    monkeypatch.setattr(program_spans, "_ring", lambda name: [
        r for r in recs if r.name == name])


def test_feed_readers_by_hand(monkeypatch):
    """Two warm-up steps, then a window of three: only what started after
    the last warm-up dispatch ended is the window's."""
    ms = 1_000_000
    recs = [Rec("train.step.dispatch", t * ms, (t + 2) * ms)
            for t in (0, 100, 200, 300, 400)]
    recs += [Rec("data.stage.starved", 50 * ms, 90 * ms),      # warm-up
             Rec("data.stage.starved", 150 * ms, 156 * ms),
             Rec("data.stage.starved", 250 * ms, 253 * ms),
             Rec("data.stage.h2d", 160 * ms, 164 * ms),
             Rec("data.stage.h2d", 260 * ms, 262 * ms),
             Rec("data.assemble.batch", 170 * ms, 200 * ms),
             Rec("data.assemble.batch", 171 * ms, 181 * ms),
             Rec("data.iterator.open", 10 * ms, 60 * ms),       # warm-up
             Rec("data.iterator.open", 300 * ms, 325 * ms)]
    _ring(monkeypatch, recs)
    obs = {"counters": {"steps": 3}}
    read = {m: _reader(m).read(obs) for m in FEED_METRICS}
    assert read["feed_starved_ms.train"] == pytest.approx(3.0)   # 9 ms / 3
    assert read["h2d_ms.train"] == pytest.approx(3.0)            # 6 ms / 2
    assert read["host_batch_ms.train"] == pytest.approx(20.0)    # 40 ms / 2
    assert read["epoch_open_ms.train"] == pytest.approx(25.0)
    # the window's dispatches start at 200, 300, 400 ms and take 2 ms each
    assert _reader("step_dispatch_ms.train").read(obs) == pytest.approx(2.0)
    assert _reader("step_gap_max_ms.train").read(obs) == pytest.approx(100.0)
    # no step, no ring (an older program), fewer records than steps
    assert _reader("h2d_ms.train").read({"counters": {}}) is None
    assert _reader("h2d_ms.train").read({"counters": {"steps": 9}}) is None
    monkeypatch.setattr(program_spans, "_ring", lambda name: None)
    assert [_reader(m).read(obs)
            for m in FEED_METRICS + STEP_METRICS] == [None] * 6


def _op(name, start, end):
    return {"name": name, "start_ns": start, "end_ns": end,
            "self_s": (end - start) / 1e9, "stats": {}}


def _layer_obs():
    """One device, two executions of the step of 100 us each: 30 us of an
    op the map calls encoder, 50 us of one whose name holds its own scope
    path (decoder), 20 us of one nobody names."""
    ops, modules, busy = [], [], []
    for base in (0, 1000):
        at = base * 1000
        ops += [_op("%fusion.1 = f32[8]{0} fusion(%p)", at, at + 30_000),
                _op('%fusion.2 = f32[8]{0} fusion(%p), metadata={op_name='
                    '"jit(step)/transpose(jvp(decoder))/mul"}',
                    at + 30_000, at + 80_000),
                _op("%copy.3 = f32[8]{0} copy(%p)", at + 80_000, at + 100_000)]
        modules.append((at, at + 100_000, "jit__train_step_impl(7)"))
        busy.append([at, at + 100_000])
    return {"trace": {"devices": [{"ops": ops, "modules": modules,
                                   "busy": busy}]},
            "counters": {"step_program": "_train_step_impl"}}


def test_layer_readers_by_hand(monkeypatch):
    from mine_tpu.telemetry import programs
    programs.reset()
    monkeypatch.setattr(program_spans, "_classifiers", {})
    obs = _layer_obs()
    # nobody registered the program: no reading
    assert _reader("encoder_ms.train").read(obs) is None
    monkeypatch.setattr(program_spans, "_classifiers", {})
    asked = []

    def text_fn():
        asked.append(1)
        return ('  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata='
                '{op_name="jit(step)/jvp(encoder)/conv" source_line=3}\n'
                '  %copy.3 = f32[8]{0} copy(%p)\n')

    programs.register("_train_step_impl", text_fn)
    try:
        read = {m: _reader(m).read(obs) for m in LAYER_METRICS}
    finally:
        programs.reset()
    assert read["encoder_ms.train"] == pytest.approx(0.030)
    assert read["decoder_ms.train"] == pytest.approx(0.050)
    assert read["render_ms.train"] == 0.0
    assert read["loss_pyramid_ms.train"] == 0.0
    assert read["optimizer_ms.train"] == 0.0
    assert read["scope_unattributed.train"] == pytest.approx(20.0)
    assert asked == [1]   # the text was asked for once, by six readers
    # no device plane (the CPU rehearsal): nothing to read
    assert _reader("encoder_ms.train").read(
        dict(obs, trace=None)) is None


@pytest.mark.parametrize("cell,expect", [
    ("tiny_train", FEED_METRICS[:3] + STEP_METRICS),
    ("tiny_serve", SERVE_METRICS)])
def test_rehearsal_prints_the_program_span_metrics(bench_copy, capsys, cell,
                                                   expect):
    rc = run.run(["--workload", cell, "--seed", "11", "--seconds", "2",
                  "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True, line
    assert set(expect) <= set(line["metrics"]), line["metrics"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    if cell == "tiny_serve":
        assert 0.0 <= got["batcher_wait_share.serve"] <= 100.0
        assert 0.0 <= got["host_busy_share.serve"] <= 100.0
        assert got["batcher_unaccounted_share.serve"] < 10.0
        parts = (got["pad_place_ms.serve"] + got["dispatch_wait_ms.serve"]
                 + got["readback_ms.serve"])
        assert parts <= got["render_call_ms.serve"]
        assert parts >= 0.8 * got["render_call_ms.serve"]
    else:
        assert got["h2d_ms.train"] > 0 and got["host_batch_ms.train"] > 0
        # the inside twin of the benchmark's own span round next(staged)
        assert got["feed_starved_ms.train"] <= got["feed_wait_ms.train"] + 1
        # no device plane on the CPU: the layer split is left out
        assert not set(LAYER_METRICS) & set(got)
