"""The token server's driver end to end on the CPU at a small size, through
the test-only seam of conftest.py: result keys, `correct`, the reference
check, the counters' metrics, and no device metric in a rehearsal. The cell
arrives as new files and entries; `bench_copy` asserts on the way out that no
file that was there was edited."""

import json
import os

import pytest

from benchmark import harness, run

from conftest import _write  # noqa: E402  (the fixtures' own helper)

TINY_MOE = {
    "lm.hidden_size": 64, "lm.intermediate_size": 96,
    "lm.moe_intermediate_size": 32, "lm.num_hidden_layers": 3,
    "lm.num_attention_heads": 4, "lm.q_lora_rank": 48, "lm.kv_lora_rank": 32,
    "lm.qk_nope_head_dim": 16, "lm.qk_rope_head_dim": 8, "lm.v_head_dim": 16,
    "lm.n_routed_experts": 16, "lm.num_experts_per_tok": 4,
    "lm.vocab_size": 512, "lm.experts_held": 4, "lm.expert_offset": 4,
    "lm.vocab_held": 128, "serve.lm.max_step_tokens": 32,
    "serve.lm.max_running": 4, "serve.lm.page_size": 8,
    # (a cache that keeps every document: `cache.last` reads a flagged
    # request's document after the window; tests/test_lm_serve.py evicts)
    "serve.lm.cache_tokens": 16384, "serve.lm.chunk_buckets": [8, 32],
    "serve.lm.context_buckets": [64, 160]}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
COUNTER_METRICS = {"sched_ms.serve", "step_fill.serve", "prefill_share.serve",
                   "cache_hit_share.serve", "expert_load_max_over_mean.serve",
                   "ttft_p50_ms.serve", "token_gap_p50_ms.serve",
                   "token_gap_p95_ms.serve"}
TRACE_METRICS = {"mla_ms.serve", "moe_ms.serve", "dense_mlp_ms.serve",
                 "head_ms.serve", "step_device_ms.serve", "mfu.serve",
                 "mla_prefill_roofline.serve", "mla_decode_roofline.serve",
                 "moe_experts_roofline.serve", "device_idle.serve"}


@pytest.fixture
def moe_copy(bench_copy):
    """`bench_copy` plus a tiny cell of the new configuration's kind."""
    bdir = os.path.join(bench_copy, "benchmark")
    _write(os.path.join(bdir, "configs", "tiny_moe.json"), {
        "name": "tiny_moe", "source": "test only",
        "yaml": "mine_tpu/configs/params_kimi_k2p5.yaml",
        "overrides": TINY_MOE, "reduced": sorted(TINY_MOE),
        "as_run": {"model.family": "moe_mla", "lm.n_group": 1}})
    _write(os.path.join(bdir, "traffic", "tiny_docqa.json"), {
        "driver": "lm_serve_closed_loop", "workers": 3,
        "resident_documents": 4,
        "document_tokens": {"median": 40, "sigma": 0.5, "min": 16,
                            "max": 100},
        "new_document_probability": 0.2, "zipf_exponent": 1.0,
        "question_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
        "answer_tokens": {"median": 8, "sigma": 0.4, "min": 4, "max": 20},
        "warmup_seconds": 0.3, "script_seed": 3,
        "reference_requests": 2,
        "reference_decode_steps": 3, "reference_max_tokens": 100,
        "trace_seconds": 0.5})
    path = os.path.join(bench_copy, "BENCHMARK.json")
    manifest = harness.load_json(path)
    manifest["configs"].append({
        "name": "tiny_moe", "source": "test only",
        "file": "benchmark/configs/tiny_moe.json",
        "reduced": sorted(TINY_MOE), "why": "CPU rehearsal"})
    manifest["workloads"].append({
        "name": "tiny_docqa", "config": "tiny_moe", "traffic": "tiny_docqa",
        "chips": 1, "why": "CPU rehearsal"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "kimi_serve_docqa" in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["tiny_docqa"]
    _write(path, manifest)
    return bench_copy


def _run(capsys, *argv):
    rc = run.run(list(argv))
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_lm_serve_end_to_end_run(moe_copy, capsys):
    rc, line, out = _run(capsys, "--workload", "tiny_docqa", "--seed",
                         str(2**31 + 4321), "--seconds", "2", "--trace", "0")
    assert rc == 0 and set(line) == RESULT_KEYS
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True, out[-12:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_views_per_s", "setup_s"}
    assert line["metrics"]["serve_views_per_s"]["value"] > 0
    text = "\n".join(out)
    from benchmark import reference_moe_mla
    for name in reference_moe_mla.TOLERANCES:   # every quantity was read
        assert "'%s'" % name in text, name
    assert "'matches_reference': True" in text
    assert "'no_token_dropped': True" in text and "requests_per_s" in text
    assert "'kind': 'question'" in text       # one of each kind was checked


def test_lm_serve_traced_run_prints_counters_and_no_device_metric(moe_copy,
                                                                  capsys):
    rc, line, out = _run(capsys, "--workload", "tiny_docqa", "--seed", "9",
                         "--seconds", "2", "--trace", "1")
    assert rc == 0 and line["correct"] is True, out[-12:]
    got = set(line["metrics"])
    assert COUNTER_METRICS <= got, COUNTER_METRICS - got
    # the accepted readers of a request's latency, unchanged, read this cell
    assert {"latency_p50_ms.serve", "latency_p95_ms.serve"} <= got
    assert not got & TRACE_METRICS    # no device plane on the CPU
    assert 0 < line["metrics"]["step_fill.serve"]["value"] <= 100
    assert 0 < line["metrics"]["cache_hit_share.serve"]["value"] < 100
    assert line["metrics"]["expert_load_max_over_mean.serve"]["value"] >= 1
    assert "breakdown" not in line and "setup_s" not in line["metrics"]


def test_new_serve_readers_are_silent_on_the_gallery_cell(bench_copy, capsys):
    """conftest.py appends its tiny MINE serve cell to every listed `.serve`
    metric, the new ones too: each new reader returns nothing there."""
    rc, line, out = _run(capsys, "--workload", "tiny_serve", "--seed", "7",
                         "--seconds", "2", "--trace", "1")
    assert rc == 0 and line["correct"] is True, out[-12:]
    assert not (COUNTER_METRICS | TRACE_METRICS) - {"device_idle.serve"} & set(
        line["metrics"])


def test_every_seed_is_given_the_same_work():
    """The scripts' shape is the traffic file's; the ids are the seed's."""
    import numpy as np

    from benchmark.drivers.lm_serve_closed_loop import Traffic
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "traffic", "docqa_closed16.json")
    wl = harness.load_json(path)

    def shape(t):
        return ([len(d) for _, d in t.resident],
                [[(e["question"], e["answer"], e["rank"], e["new"])
                  for e in script] for script in t.scripts])
    a = Traffic(wl, 1, 20480, script_len=32)
    b = Traffic(wl, 2**31 + 7, 20480, script_len=32)
    assert shape(a) == shape(b)
    assert not np.array_equal(a.pool[:4096], b.pool[:4096])
    kinds = [e[3] is not None for script in shape(a)[1] for e in script]
    assert 0.1 < np.mean(kinds) < 0.3        # a probability, not a slot
    other = Traffic(dict(wl, script_seed=wl["script_seed"] + 1), 1, 20480,
                    script_len=32)
    assert shape(other) != shape(a)


def test_roofline_prices_a_step_from_its_spans_fields():
    from benchmark import roofline_moe_mla as F
    shapes = {"hidden": 7168, "heads": 64, "q_lora_rank": 1536,
              "kv_lora_rank": 512, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "v_head_dim": 128,
              "dense_intermediate": 18432, "moe_intermediate": 2048,
              "layers": 7, "moe_layers": 6, "n_routed_experts": 384,
              "vocab": 20480}
    peaks = {"peak_tflops_bf16": 197.0, "hbm_gbps": 819.0}
    # attention's matmuls against weights: 101.1 M parameters a layer
    assert F.projection_flops_per_token(shapes) == 2 * 101122048
    step = {"tokens": 2048, "decode": 16, "prefill": 2032,
            "prefill_start": 8192, "decode_context": 16 * 13000,
            "expert_pairs": 6 * 512, "experts_touched": 72,
            "sampled_rows": 17}
    # outside attention and the head, with a quarter pair a token and layer:
    # about 2.9 GFLOP a token
    no_attention = dict(step, prefill=0, prefill_start=0, decode_context=0,
                        sampled_rows=0)
    per_token = F.step_model_flops(shapes, no_attention) / 2048
    assert 2.7e9 < per_token < 3.1e9
    assert F.mla_prefill_floor_s(shapes, step, peaks) > 0
    # decode in latent space is bound by the cache's bytes, experts by
    # their weights' bytes at this load
    d = F.mla_decode_floor_s(shapes, step, peaks)
    assert abs(d - 7 * 2 * (16 * 13000 * 576 + 16 * 64 * 1088) / 819e9) < 1e-9
    e = F.moe_experts_floor_s(shapes, step, peaks)
    assert abs(e - 2 * (72 * 3 * 7168 * 2048 + 3072 * 2 * 7168) / 819e9) < 1e-9
    assert F.mla_decode_floor_s(shapes, dict(step, decode=0), peaks) == 0
