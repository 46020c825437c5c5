"""Telemetry layer (mine_tpu/telemetry): the contracts everything else now
leans on, each asserted here:

  * histogram quantiles track numpy percentiles within the documented
    bucket-width bound, clamped to the observed range;
  * counter/gauge/registry snapshot semantics (types, prefixes, conflicts);
  * the JSONL sink degrades to a warn-once no-op on an unwritable path —
    instrumentation must never kill the run it observes;
  * every emitted line round-trips through the mtpu-ev1 validator;
  * span timers nest into dotted paths and unwind on exceptions;
  * the frozen st1 step-time line: format -> parse round-trip, legacy-form
    parity, unknown-tail tolerance (the append-only evolution rule);
  * tools/step_breakdown.py really reads through the ONE shared parser;
  * the instrumented serve render path is BITWISE-unchanged by telemetry
    being on or off (host-side-only is a testable property, not a comment).
"""

import json
import os
import sys

import numpy as np
import pytest

from mine_tpu import telemetry
from mine_tpu.telemetry import events as tevents
from mine_tpu.telemetry import stepline
from mine_tpu.telemetry.registry import Histogram, MetricsRegistry
from mine_tpu.telemetry import spans
from mine_tpu.telemetry.spans import span


@pytest.fixture
def clean_sink(monkeypatch):
    """Isolate the process-wide sink: no env funnel, nothing configured;
    re-arm the env-var check afterwards so an outer harness's
    MINE_TPU_TELEMETRY_EVENTS keeps working for later tests."""
    monkeypatch.delenv(tevents.ENV_VAR, raising=False)
    tevents.reset()
    yield
    tevents.reset()


# ---------------- histogram math ----------------

def test_histogram_quantiles_match_numpy():
    """Default latency buckets grow 1.3x, so an interpolated quantile lies
    within its containing bucket: relative error vs the exact numpy
    percentile is bounded by the growth factor."""
    rng = np.random.RandomState(7)
    samples = np.exp(rng.normal(2.0, 1.5, size=5000))  # 0.05..120k-ish ms
    h = Histogram("t")
    for v in samples:
        h.record(float(v))
    for q in (0.5, 0.9, 0.99):
        exact = np.percentile(samples, 100 * q)
        approx = h.quantile(q)
        assert abs(approx - exact) <= 0.35 * exact + 1e-9, (q, approx, exact)
    assert h.count == len(samples)
    np.testing.assert_allclose(h.sum, samples.sum(), rtol=1e-9)
    np.testing.assert_allclose(h.mean, samples.mean(), rtol=1e-9)


def test_histogram_quantile_clamped_to_observed_range():
    h = Histogram("t", edges=(1.0, 10.0, 100.0))
    h.record(3.0)
    h.record(4.0)
    # interpolation within the (1, 10] bucket would report up to 10;
    # the clamp keeps every quantile inside [min, max] actually seen
    assert h.quantile(0.0) == 3.0
    assert 3.0 <= h.quantile(0.5) <= 4.0
    assert h.quantile(1.0) == 4.0


def test_histogram_overflow_bucket_and_nan():
    h = Histogram("t", edges=(1.0, 2.0))
    h.record(float("nan"))  # dropped, not poisoning sum/mean
    assert h.count == 0
    h.record(1000.0)  # overflow bucket: p99 reports the observed max
    assert h.count == 1 and h.quantile(0.99) == 1000.0


def test_histogram_empty_is_nan():
    h = Histogram("t", edges=(1.0,))
    assert np.isnan(h.quantile(0.5))
    assert h.snapshot() == {"count": 0}


def test_histogram_rejects_bad_edges_and_q():
    with pytest.raises(ValueError):
        Histogram("t", edges=(2.0, 1.0))
    h = Histogram("t", edges=(1.0,))
    with pytest.raises(ValueError):
        h.quantile(1.5)


# ---------------- registry semantics ----------------

def test_registry_counter_gauge_snapshot():
    reg = MetricsRegistry()
    reg.counter("a.hits").inc()
    reg.counter("a.hits").inc(3)  # get-or-create: same counter
    reg.gauge("a.bytes").set(12.5)
    reg.histogram("b.ms").record(2.0)
    snap = reg.snapshot()
    assert snap["a.hits"] == 4 and isinstance(snap["a.hits"], int)
    assert snap["a.bytes"] == 12.5
    assert snap["b.ms"]["count"] == 1
    # prefix filter + JSON-safety (what the metrics.snapshot event carries)
    assert set(reg.snapshot("a.")) == {"a.hits", "a.bytes"}
    json.dumps(reg.snapshot())
    reg.reset()
    assert reg.snapshot() == {}


def test_registry_type_conflicts_raise():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    reg.histogram("h", edges=(1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("h", edges=(5.0,))
    with pytest.raises(ValueError):
        reg.counter("x").inc(-1)


# ---------------- event sink ----------------

def test_sink_roundtrip_and_validation(tmp_path, clean_sink):
    path = str(tmp_path / "ev.jsonl")
    tevents.configure(path)
    assert telemetry.emit("unit.test", n=3, nested={"a": [1, 2]},
                          arr=np.float32(1.5))
    tevents.current_sink().close()
    assert tevents.validate_file(path) == []
    (ev,) = tevents.read_events(path)
    assert ev["schema"] == tevents.SCHEMA and ev["kind"] == "unit.test"
    assert ev["n"] == 3 and ev["nested"] == {"a": [1, 2]}
    assert ev["arr"] == 1.5  # numpy degraded to a JSON scalar, not killed
    assert isinstance(ev["ts"], float)


def test_validate_line_rejects_bad_shapes():
    ok = json.dumps({"schema": tevents.SCHEMA, "ts": 1.0, "kind": "k"})
    assert tevents.validate_line(ok) is None
    assert tevents.validate_line("") is None  # blank lines tolerated
    assert tevents.validate_line("not json") is not None
    assert tevents.validate_line("[1,2]") is not None
    assert tevents.validate_line(json.dumps({"ts": 1.0, "kind": "k"})) \
        is not None
    assert tevents.validate_line(json.dumps(
        {"schema": "mtpu-ev999", "ts": 1.0, "kind": "k"})) is not None
    assert tevents.validate_line(json.dumps(
        {"schema": tevents.SCHEMA, "ts": "late", "kind": "k"})) is not None
    assert tevents.validate_line(json.dumps(
        {"schema": tevents.SCHEMA, "ts": 1.0, "kind": ""})) is not None


def test_sink_unwritable_degrades_with_one_warning(tmp_path, clean_sink,
                                                  caplog):
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("file where a directory is needed")
    sink = tevents.configure(str(blocker / "events.jsonl"))
    with caplog.at_level("WARNING", logger=tevents.__name__):
        assert telemetry.emit("a") is False  # degraded, did not raise
        assert telemetry.emit("b") is False
    warnings = [r for r in caplog.records
                if "event sink failed" in r.getMessage()]
    assert len(warnings) == 1  # ONE warning, then silence
    assert sink.broken and sink.dropped == 2 and sink.emitted == 0


def test_unconfigured_emit_is_cheap_noop(clean_sink):
    assert telemetry.emit("nobody.listening") is False


def test_env_var_funnel_and_explicit_override(tmp_path, clean_sink,
                                              monkeypatch):
    env_path = str(tmp_path / "env.jsonl")
    monkeypatch.setenv(tevents.ENV_VAR, env_path)
    tevents.reset()
    # ensure_configured: the env var outranks the caller's default
    sink = tevents.ensure_configured(str(tmp_path / "default.jsonl"))
    assert sink.path == env_path
    telemetry.emit("env.owned")
    # a second ensure_configured never replaces an existing sink
    assert tevents.ensure_configured(str(tmp_path / "other.jsonl")) is sink
    # explicit configure outranks everything
    explicit = str(tmp_path / "explicit.jsonl")
    tevents.configure(explicit)
    telemetry.emit("explicit.owned")
    tevents.current_sink().close()
    assert [e["kind"] for e in tevents.read_events(env_path)] == ["env.owned"]
    assert [e["kind"] for e in tevents.read_events(explicit)] \
        == ["explicit.owned"]


# ---------------- spans ----------------

def test_span_nesting_paths_and_histograms(tmp_path, clean_sink):
    """Names are absolute; nesting is the record's `parent`. emit=True
    writes the span event at once."""
    tevents.configure(str(tmp_path / "ev.jsonl"))
    reg = MetricsRegistry()
    with span("outer", emit=True, registry=reg) as outer:
        assert spans.current() == outer.span_id
        with span("outer.inner", emit=True, registry=reg,
                  detail="x") as inner:
            assert spans.current() == inner.span_id
            assert inner.parent == outer.span_id
        assert spans.current() == outer.span_id
    assert spans.current() is None
    assert reg.histogram("outer_ms").count == 1
    assert reg.histogram("outer.inner_ms").count == 1
    tevents.current_sink().close()
    events = tevents.read_events(str(tmp_path / "ev.jsonl"))
    assert [e["name"] for e in events] == ["outer.inner", "outer"]
    assert all(e["kind"] == "span" and e["ok"] for e in events)
    assert events[0]["detail"] == "x"


def test_span_unwinds_and_propagates_on_exception(clean_sink):
    reg = MetricsRegistry()
    with pytest.raises(RuntimeError):
        with span("boom", registry=reg):
            raise RuntimeError("inner failure")
    assert spans.current() is None  # stack unwound
    assert reg.histogram("boom_ms").count == 1  # failure time still counts
    assert spans.records("boom")[-1].fields["ok"] is False


# ---------------- the frozen st1 step line ----------------

_TIMES = {"step_ms": 812.04, "host_wait_ms": 590.1, "device_ms": 221.9,
          "h2d_ms": 35.25}


def test_stepline_format_parse_roundtrip():
    line = stepline.format_step_line(_TIMES, data_errors=7)
    assert line.startswith("time: schema=st1 ")
    # frozen key order — the schema contract, not a formatting accident
    assert line == ("time: schema=st1 step_ms=812.0 host_wait_ms=590.1 "
                    "device_ms=221.9 h2d_ms=35.2 data_errors=7")
    rec = stepline.parse_line("        " + line)
    assert rec == {"step": 812.0, "host_wait": 590.1, "device": 221.9,
                   "h2d": 35.2, "data_errors": 7}


def test_stepline_legacy_parity():
    """The pre-st1 printf form (with and without PR-4's data_errors tail)
    parses to the same record — old logs keep summarizing."""
    legacy = ("time: step = 812.0 ms host_wait = 590.1 ms "
              "device = 221.9 ms h2d = 35.2 ms")
    st1 = stepline.format_step_line(_TIMES, data_errors=0)
    assert stepline.parse_line(legacy) == stepline.parse_line(st1)
    with_errors = legacy + " data_errors = 7"
    assert stepline.parse_line(with_errors)["data_errors"] == 7


def test_stepline_append_only_evolution():
    # unknown APPENDED keys pass through; a different schema tag is skipped
    line = stepline.format_step_line(_TIMES, 0) + " new_metric_ms=1.5"
    rec = stepline.parse_line(line)
    assert rec["new_metric"] == 1.5 and rec["step"] == 812.0
    assert stepline.parse_line(
        line.replace("schema=st1", "schema=st99")) is None
    # torn line (missing required keys) is skipped, not misparsed
    assert stepline.parse_line("time: schema=st1 step_ms=1.0") is None


def test_parse_lines_aggregates_only_time_keys():
    lines = ["noise", stepline.format_step_line(_TIMES, 1),
             "time: step = 100.0 ms host_wait = 50.0 ms device = 50.0 ms "
             "h2d = 5.0 ms"]
    samples = stepline.parse_lines(lines)
    assert set(samples) == set(stepline.TIME_KEYS)
    assert samples["step"] == [812.0, 100.0]


def test_step_breakdown_tool_uses_shared_parser():
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import step_breakdown
    assert step_breakdown.parse_lines is stepline.parse_lines
    assert step_breakdown.KEYS == stepline.TIME_KEYS


# ---------------- train-loop logging through the layer ----------------

def test_log_training_emits_st1_line_and_registry(tmp_path, clean_sink):
    """One _log_training call on a stubbed loop: the frozen st1 line lands
    in the log, train.* histograms and the train.step event are recorded —
    all from host floats (nothing here ever touches a device value)."""
    from types import SimpleNamespace

    from mine_tpu.train.loop import TIME_METER_KEYS, TrainLoop
    from mine_tpu.train.step import SynthesisTrainer
    from mine_tpu.utils import AverageMeter
    from tests.test_train import tiny_config

    tevents.configure(str(tmp_path / "ev.jsonl"))
    telemetry.REGISTRY.reset()
    logged = []
    from collections import deque
    stub = SimpleNamespace(
        config=tiny_config(),
        trainer=SimpleNamespace(
            steps_per_epoch=10, LOG_LR=SynthesisTrainer.LOG_LR,
            log_summary=lambda m: SynthesisTrainer.log_summary(None, m),
            log_gauges=lambda m, times: {}),
        telem=SimpleNamespace(enabled=True),
        time_meters={k: AverageMeter("time_" + k, ":.1f")
                     for k in TIME_METER_KEYS},
        train_meters={},
        _step_hist=deque(maxlen=64),  # ops-plane state (PR 12)
        recorder=None,  # flight recorder off (PR 15)
        _ops_state={"gstep": 0, "epoch": 0, "epochs": 0,
                    "guard_consecutive": 0.0, "data_errors": 0,
                    "data_errors_delta": 0},
        _log=lambda msg, *a: logged.append(msg % a if a else msg),
        _tb=lambda *a: None)
    m = {"loss": 1.5, "loss_rgb_src": 0.1, "loss_ssim_src": 0.2,
         "loss_disp_pt3dsrc": 0.3, "loss_rgb_tgt": 0.4, "loss_ssim_tgt": 0.5,
         "loss_disp_pt3dtgt": 0.6, "psnr_tgt": 20.0, "skipped_steps": 2.0}
    times = {"step_ms": 812.0, "host_wait_ms": 590.1, "device_ms": 221.9,
             "h2d_ms": 35.2}
    TrainLoop._log_training(stub, epoch=0, step=9, gstep=10, m=m, times=times)

    st1_lines = [ln for entry in logged for ln in entry.splitlines()
                 if stepline.parse_line(ln)]
    assert len(st1_lines) == 1
    assert stepline.parse_line(st1_lines[0])["step"] == 812.0
    assert "schema=st1" in st1_lines[0]
    for k in TIME_METER_KEYS:
        assert telemetry.REGISTRY.get("train." + k).count == 1
    assert telemetry.REGISTRY.get("train.guard.skipped_steps").value == 2.0
    tevents.current_sink().close()
    (ev,) = tevents.read_events(str(tmp_path / "ev.jsonl"))
    assert ev["kind"] == "train.step" and ev["gstep"] == 10
    assert ev["step_ms"] == 812.0 and ev["data_errors"] >= 0


# ---------------- profiler window ----------------

def test_profile_window_validation_and_resume_skip(tmp_path):
    from mine_tpu.telemetry.profiler import ProfileWindow

    with pytest.raises(ValueError):
        ProfileWindow([5, 3], str(tmp_path))
    with pytest.raises(ValueError):
        ProfileWindow([0, 3], str(tmp_path))
    with pytest.raises(ValueError):
        ProfileWindow([7], str(tmp_path))
    # no steps: permanently disabled, every hook is a cheap no-op
    w = ProfileWindow((), str(tmp_path))
    w.maybe_start(1)
    w.maybe_stop(1)
    w.stop()
    assert not w.active and w.done
    # resumed past the window start: skipped (a partial trace would lie
    # about the steps it claims), with a warning
    w = ProfileWindow([3, 5], str(tmp_path))
    w.maybe_start(10)
    assert w.done and not w.active


def test_profile_window_traces_exact_steps(tmp_path, clean_sink):
    """[2, 3] brackets exactly steps 2..3: idle before 2, active through 3,
    stopped after — and the trace dir lands in the event stream."""
    from mine_tpu.telemetry.profiler import ProfileWindow

    tevents.configure(str(tmp_path / "ev.jsonl"))
    trace_dir = str(tmp_path / "trace")
    w = ProfileWindow([2, 3], trace_dir)
    w.maybe_start(1)
    assert not w.active
    w.maybe_stop(1)
    w.maybe_start(2)
    if w.done and not w.active:  # profiler unavailable on this backend:
        return                   # the non-fatal degrade IS the contract
    assert w.active
    w.maybe_stop(2)
    assert w.active  # stop step not reached yet
    w.maybe_start(3)  # already active: no-op
    w.maybe_stop(3)
    assert not w.active and w.done
    tevents.current_sink().close()
    events = [e for e in tevents.read_events(str(tmp_path / "ev.jsonl"))
              if e["kind"] == "profile.window"]
    assert events and events[0]["trace_dir"] == trace_dir
    assert events[0]["start_step"] == 2 and events[0]["stop_step"] == 3
    assert os.path.isdir(trace_dir)


# ---------------- telemetry cannot change numerics ----------------

def test_serve_render_bitwise_unchanged_by_telemetry(tmp_path, clean_sink):
    """The acceptance contract: the instrumented serve path produces
    BITWISE-identical renders with telemetry fully on (sink + registry)
    vs fully off — metrics are host-side observations, never participants."""
    from mine_tpu.serve import MPICache, RenderEngine

    rng = np.random.RandomState(0)
    planes = rng.uniform(0.0, 1.0, (4, 4, 16, 16)).astype(np.float32)
    disparity = np.linspace(1.0, 0.1, 4).astype(np.float32)
    K = np.array([[20.0, 0, 8], [0, 20.0, 8], [0, 0, 1]], np.float32)
    poses = np.tile(np.eye(4, dtype=np.float32), (3, 1, 1))
    poses[:, 0, 3] = [0.0, 0.01, 0.02]

    def render_once():
        engine = RenderEngine(cache=MPICache(quant="bf16"))
        engine.put("img", planes[:, 0:3], planes[:, 3:4], disparity, K)
        return engine.render("img", poses)

    rgb_off, depth_off = render_once()  # sink unconfigured, cheap no-ops
    tevents.configure(str(tmp_path / "ev.jsonl"))
    telemetry.counter("serve.cache.hits")  # registry warm too
    rgb_on, depth_on = render_once()
    np.testing.assert_array_equal(rgb_off, rgb_on)
    np.testing.assert_array_equal(depth_off, depth_on)
    # and the instrumentation really observed the run
    assert telemetry.REGISTRY.get("serve.cache.hits").value >= 1
    tevents.current_sink().close()
    assert tevents.validate_file(str(tmp_path / "ev.jsonl")) == []


# ---------------- the SLO bench (subprocess smoke) ----------------

@pytest.mark.slow
def test_serve_slo_smoke_emits_parseable_curve(tmp_path):
    """bench.py serve_slo on CPU smoke: one parseable offered:p50:p99:
    achieved curve line, a knee line, and schema-clean slo_point events."""
    import re
    import subprocess

    events = str(tmp_path / "ev.jsonl")
    env = dict(os.environ, MINE_TPU_BENCH_SMOKE="1", JAX_PLATFORMS="cpu",
               MINE_TPU_TELEMETRY_EVENTS=events)
    out = subprocess.run(
        [sys.executable, "-c",
         "import bench; print(bench._measure('serve_slo')[0])"],
        capture_output=True, text=True, env=env, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    # bench routes variant progress to stderr (stdout carries the JSON
    # result line in a sweep); the curve/knee lines live there
    curve = [ln for ln in out.stderr.splitlines()
             if ln.strip().startswith("serve_slo curve:")]
    assert len(curve) == 1
    pts = re.findall(r"([\d.]+):([\d.]+):([\d.]+):([\d.]+)", curve[0])
    assert len(pts) == 5  # one point per SERVE_SLO_RATE_FRACS entry
    offered = [float(p[0]) for p in pts]
    assert offered == sorted(offered) and offered[0] > 0
    assert any("serve_slo knee:" in ln for ln in out.stderr.splitlines())
    # the knee qps _measure returned (printed to stdout) is positive
    assert float(out.stdout.splitlines()[-1]) > 0
    assert tevents.validate_file(events) == []
    points = [e for e in tevents.read_events(events)
              if e["kind"] == "serve.slo_point"]
    # 5 curve points plus the ONE deliberate admission-on overload point
    # (flagged overload=True so curve consumers can exclude it)
    assert sum(1 for e in points if not e.get("overload")) == 5
    assert sum(1 for e in points if e.get("overload")) == 1
