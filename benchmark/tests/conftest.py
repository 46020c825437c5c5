"""Fixtures of the benchmark's own tests (`python -m pytest benchmark/tests
-q`; not part of tier-1). The CPU rehearsal goes through the test-only seam
in benchmark/harness.py (REQUIRED_PLATFORM, PEAKS_FILE, ROOT,
COMPILE_CACHE_DIR), never through an option of run.py."""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY_OVERRIDES = {
    "data.name": "synthetic", "data.img_h": 64, "data.img_w": 64,
    "mpi.num_bins_coarse": 4, "model.num_layers": 18,
    "data.per_gpu_batch_size": 2, "data.visible_point_count": 32,
    "training.log_interval": 2}


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """A copy of BENCHMARK.json and benchmark/ in which a cell, a
    configuration, a traffic mix and a per-layer metric are ADDED as new
    files and entries; no file that was there is edited. The harness is
    pointed at the copy and at the CPU."""
    from benchmark import harness
    root = str(tmp_path / "checkout")
    shutil.copytree(BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__",
                                                  "tests"))
    before = _tree(root)
    bdir = os.path.join(root, "benchmark")
    _write(os.path.join(bdir, "configs", "tiny.json"), {
        "name": "tiny", "source": "test only",
        "yaml": "mine_tpu/configs/params_llff.yaml",
        "overrides": TINY_OVERRIDES, "reduced": sorted(TINY_OVERRIDES),
        "as_run": {"data.img_h": 64, "mpi.num_bins_coarse": 4}})
    _write(os.path.join(bdir, "traffic", "tiny_train_loop.json"), {
        "driver": "train_loop",
        "dataset": {"kind": "synthetic_pairs", "num_views": 9,
                    "num_points": 32},
        "warmup": {"steps_before_epoch_end": 1, "steps_after_epoch_start": 1},
        "trace_seconds": 0.5})
    _write(os.path.join(bdir, "traffic", "tiny_gallery.json"), {
        "driver": "serve_open_loop", "images": 6, "zipf_exponent": 1.0,
        "rate_views_per_s": 20.0, "resident_at_start": True,
        "engine": {"warp_impl": "xla"}, "reference_views": 2,
        "config_overrides": {"serve.max_bucket": 4, "serve.max_requests": 4},
        "trace_seconds": 0.5})
    _write(os.path.join(bdir, "traffic", "tiny_gallery_churn.json"), {
        "driver": "serve_open_loop", "images": 6, "zipf_exponent": 0.8,
        "rate_views_per_s": 10.0, "resident_at_start": False,
        "engine": {"warp_impl": "xla"}, "reference_views": 2,
        # room for three of the six images (4 planes x 4 ch x 64 x 64 bf16)
        "config_overrides": {"serve.max_bucket": 4, "serve.max_requests": 4,
                             "serve.cache_bytes": 3 * 131200},
        "trace_seconds": 0.5})
    with open(os.path.join(bdir, "layer_metrics", "steps_seen.test.py"),
              "w") as f:
        f.write('LAYER = "train step"\nUNIT = "steps"\n'
                'SOURCE = "program_counter"\nMOVES = "train_images_per_s"\n'
                '\n\ndef read(obs):\n    return obs["counters"].get("steps")\n')
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny", "source": "test only",
        "file": "benchmark/configs/tiny.json",
        "reduced": sorted(TINY_OVERRIDES), "why": "CPU rehearsal"})
    manifest["workloads"] += [
        {"name": "tiny_train", "config": "tiny",
         "traffic": "tiny_train_loop", "chips": 1, "why": "CPU rehearsal"},
        {"name": "tiny_serve", "config": "tiny", "traffic": "tiny_gallery",
         "chips": 1, "why": "CPU rehearsal"},
        {"name": "tiny_serve_churn", "config": "tiny",
         "traffic": "tiny_gallery_churn", "chips": 1, "why": "CPU rehearsal"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            kind = "serve" if "serve" in m["name"] else "train"
            m["workloads"] = m["workloads"] + ["tiny_" + kind] + (
                ["tiny_serve_churn"] if kind == "serve" else [])
    manifest["per_layer"].append({
        "name": "steps_seen.test", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_images_per_s", "workloads": ["tiny_train"]})
    _write(os.path.join(root, "BENCHMARK.json"), manifest)
    peaks = os.path.join(root, "peaks_cpu.json")
    _write(peaks, {"cpu": {"peak_tflops_bf16": 1.0, "hbm_gbps": 10.0,
                           "hbm_gb": 1.0, "source": "test only"}})
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(harness, "PEAKS_FILE", peaks)
    monkeypatch.setattr(harness, "COMPILE_CACHE_DIR",
                        str(tmp_path / "jax_cache"))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    yield root
    after = _tree(root)
    # adding a cell edited no file that was there
    assert {k: v for k, v in after.items() if k in before} == before


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def _tree(root):
    out = {}
    for d, dirs, files in os.walk(os.path.join(root, "benchmark")):
        dirs[:] = [x for x in dirs if x not in ("__pycache__", ".cache")]
        for name in files:
            path = os.path.join(d, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = f.read()
    return out
