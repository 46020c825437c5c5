"""Both drivers end to end on the CPU at a tiny shape, through the test-only
seam; the result line's keys; a run without a TPU fails."""

import collections
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, run

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.mark.parametrize("cell,metric", [
    ("tiny_train", "train_images_per_s"),
    ("tiny_serve", "serve_views_per_s")])
def test_end_to_end_run(bench_copy, capsys, cell, metric):
    rc = run.run(["--workload", cell, "--seed", str(2**31 + 12345),
                  "--seconds", "2", "--trace", "0"])
    line, _ = _last_line(capsys)
    assert rc == 0
    assert set(line) == RESULT_KEYS
    assert DEVICE_KEYS <= set(line["device"])
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) >= {metric, "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


@pytest.mark.parametrize("cell,expect", [
    ("tiny_train", {"feed_wait_ms.train", "steps_seen.test"}),
    ("tiny_serve", {"queue_wait_ms.serve", "render_call_ms.serve",
                    "bucket_fill.serve", "gen_late_ms.serve"})])
def test_traced_run(bench_copy, capsys, cell, expect):
    rc = run.run(["--workload", cell, "--seed", "7", "--seconds", "2",
                  "--trace", "1"])
    line, _ = _last_line(capsys)
    assert rc == 0 and line["correct"] is True, line
    # the CPU trace holds no device plane: readers that need one return
    # nothing and the harness leaves those metrics out; the new reader,
    # added as a file, is found by its name
    assert expect <= set(line["metrics"]), line["metrics"]
    assert not any("roofline" in k or "device" in k or "pallas" in k
                   for k in line["metrics"])
    assert "breakdown" not in line and "busy_s" not in line["device"]
    assert "setup_s" not in line["metrics"]


def test_cold_cache_cell_is_data_only(bench_copy, capsys):
    """`llff_serve_churn` of PERF.md's Open questions can arrive as JSON
    alone: a cache that starts empty and holds half the images, requests
    that carry their pixels, encodes and evictions inside the window."""
    from mine_tpu import telemetry
    evicted0 = telemetry.counter("serve.cache.evictions").value
    encoded0 = telemetry.counter("serve.sync_encode").value
    rc = run.run(["--workload", "tiny_serve_churn", "--seed", "3",
                  "--seconds", "3", "--trace", "0"])
    line, _ = _last_line(capsys)
    assert rc == 0 and line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] == 30
    assert telemetry.counter("serve.sync_encode").value - encoded0 >= 6
    assert telemetry.counter("serve.cache.evictions").value > evicted0


def test_same_seed_same_inputs(bench_copy):
    serve = harness.Cell("tiny_serve").driver()
    wl = harness.Cell("tiny_serve").workload
    a = serve.schedule(wl, seed=5, seconds=3.0, n_images=6, n_poses=10)
    b = serve.schedule(wl, seed=5, seconds=3.0, n_images=6, n_poses=10)
    c = serve.schedule(wl, seed=6, seconds=3.0, n_images=6, n_poses=10)
    assert a == b and a != c
    # another seed: the same gaps and the same images, in another order
    gaps = lambda s: sorted(round(y - x, 9) for x, y in  # noqa: E731
                            zip([0.0] + s["t"][:-1], s["t"]))
    assert gaps(a) == gaps(c)
    per_image = lambda s: sorted(  # noqa: E731
        collections.Counter(s["image"]).values())
    assert per_image(a) == per_image(c)
    assert sorted(a["pose"]) == sorted(c["pose"])
    assert len(a["t"]) == round(3.0 * wl["rate_views_per_s"])


def test_no_tpu_fails(tmp_path):
    """The real run.py, no seam: JAX on the CPU is not the chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", "llff_train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())
    assert "never falls back" in proc.stderr


def test_benchmark_without_program_fails(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths`: no result, exit code other than 0."""
    import shutil
    root = tmp_path / "only_benchmark"
    shutil.copytree(harness.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(os.path.dirname(harness.BENCH_DIR),
                             "BENCHMARK.json"), root / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         "llff_train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=str(root), capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())
    assert "no program" in proc.stderr


def test_unknown_device_kind_fails(bench_copy, monkeypatch, tmp_path):
    empty = tmp_path / "no_peaks.json"
    empty.write_text("{}")
    monkeypatch.setattr(harness, "PEAKS_FILE", str(empty))
    with pytest.raises(harness.BenchError, match="no published peaks"):
        harness.require_devices(1)


def test_fewer_chips_fails(bench_copy):
    import jax
    with pytest.raises(harness.BenchError, match="asks for"):
        harness.require_devices(len(jax.devices()) + 1)
