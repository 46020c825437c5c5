"""The sparse token server's driver (drivers/lm_serve_sparse_closed_loop.py)
end to end on the CPU at a small size, through the test-only seam of
conftest.py: `correct` against benchmark/reference_dots3.py with every
quantity read, the new counters' metrics, every new reader silent where it
has nothing to read, and the roofline module's arithmetic."""

import json
import os

import pytest

from benchmark import harness, roofline_dots3, run

from conftest import _write  # noqa: E402

TINY = {
    "lm.hidden_size": 64, "lm.intermediate_size": 96,
    "lm.moe_intermediate_size": 32, "lm.num_hidden_layers": 5,
    "lm.layer_types": ["full_attention", "full_attention",
                       "sliding_attention", "sliding_attention",
                       "sliding_attention"],
    "lm.num_attention_heads": 4, "lm.q_lora_rank": 48, "lm.kv_lora_rank": 32,
    "lm.qk_nope_head_dim": 16, "lm.qk_rope_head_dim": 8, "lm.v_head_dim": 16,
    "lm.index_n_heads": 4, "lm.index_head_dim": 16, "lm.index_topk": 16,
    "lm.sliding_window_size": 17, "lm.swa_num_attention_heads": 2,
    "lm.swa_q_lora_rank": 48, "lm.swa_kv_lora_rank": 40,
    "lm.swa_qk_nope_head_dim": 24, "lm.swa_qk_rope_head_dim": 8,
    "lm.swa_v_head_dim": 16,
    "lm.n_routed_experts": 16, "lm.num_experts_per_tok": 4,
    "lm.vocab_size": 512, "lm.experts_held": 4, "lm.expert_offset": 4,
    "lm.vocab_held": 128, "serve.lm.max_step_tokens": 32,
    "serve.lm.max_running": 4, "serve.lm.page_size": 8,
    "serve.lm.cache_tokens": 16384, "serve.lm.window_cache_tokens": 1024,
    "serve.lm.chunk_buckets": [8, 32],
    "serve.lm.context_buckets": [64, 160]}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NEW_TRACE_METRICS = {"mfu_hybrid.serve", "dsa_index_ms.serve",
                     "dsa_attend_ms.serve", "swa_ms.serve",
                     "dsa_index_roofline.serve", "dsa_attend_roofline.serve",
                     "swa_roofline.serve", "attn_proj_gate_ms.serve"}
CELL = "dots3_serve_longdoc"


@pytest.fixture
def sparse_copy(bench_copy):
    """`bench_copy` plus a tiny cell of the new configuration's kind, listed
    wherever the published cell is."""
    bdir = os.path.join(bench_copy, "benchmark")
    _write(os.path.join(bdir, "configs", "tiny_sparse.json"), {
        "name": "tiny_sparse", "source": "test only",
        "yaml": "mine_tpu/configs/params_dots3_note.yaml",
        "overrides": TINY, "reduced": sorted(TINY),
        "as_run": {"model.family": "moe_mla", "lm.n_group": 1}})
    _write(os.path.join(bdir, "traffic", "tiny_longdoc.json"), {
        "driver": "lm_serve_sparse_closed_loop", "workers": 3,
        "resident_documents": 4,
        "document_tokens": {"median": 60, "sigma": 0.4, "min": 40,
                            "max": 110},
        "new_document_probability": 0.2, "zipf_exponent": 1.0,
        "question_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
        "answer_tokens": {"median": 8, "sigma": 0.4, "min": 4, "max": 20},
        "warmup_seconds": 0.3, "script_seed": 3, "reference_requests": 2,
        "reference_decode_steps": 3, "reference_max_tokens": 130,
        "trace_seconds": 0.5})
    path = os.path.join(bench_copy, "BENCHMARK.json")
    manifest = harness.load_json(path)
    manifest["configs"].append({
        "name": "tiny_sparse", "source": "test only",
        "file": "benchmark/configs/tiny_sparse.json",
        "reduced": sorted(TINY), "why": "CPU rehearsal"})
    manifest["workloads"].append({
        "name": "tiny_longdoc", "config": "tiny_sparse",
        "traffic": "tiny_longdoc", "chips": 1, "why": "CPU rehearsal"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["tiny_longdoc"]
    _write(path, manifest)
    return bench_copy


def _run(capsys, *argv):
    rc = run.run(list(argv))
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_sparse_serve_end_to_end_run(sparse_copy, capsys):
    rc, line, out = _run(capsys, "--workload", "tiny_longdoc", "--seed",
                         str(2**31 + 4321), "--seconds", "2", "--trace", "0")
    assert rc == 0 and set(line) == RESULT_KEYS
    assert line["correct"] is True, out[-12:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_views_per_s", "setup_s"}
    text = "\n".join(out)
    from benchmark import reference_dots3
    for name in reference_dots3.TOLERANCES:     # every quantity was read
        assert "'%s'" % name in text, name
    assert "'matches_reference': True" in text
    assert "'no_token_dropped': True" in text
    assert "'kind': 'question'" in text


@pytest.mark.parametrize("fault, over", [
    ("index_no_rope", {"index.q_first", "index.drift_first"}),
    ("index_w_negated", {"index.w_first", "index.drift_first"})])
def test_a_wrong_indexer_fails_the_first_full_layers_limits(
        sparse_copy, capsys, monkeypatch, fault, over):
    """An indexer that is wrong and consistent with itself (S_t an exact
    top-k of the scores the program returns, those scores right for its own
    q_I and w) passes `same.index` and `index.margin`; the first full
    layer's limits against the reference's OWN indexer fail it."""
    import sys
    from mine_tpu.models import moe_mla
    monkeypatch.syspath_prepend(os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "tools"))
    import lm_serve_fault_readings
    monkeypatch.setattr(moe_mla, "dsa_project", moe_mla.dsa_project)
    lm_serve_fault_readings.plant_sparse(fault)
    rc, line, out = _run(capsys, "--workload", "tiny_longdoc", "--seed",
                         "77", "--seconds", "1", "--trace", "0")
    sys.modules.pop("lm_serve_fault_readings", None)
    assert line["correct"] is False
    text = "\n".join(l for l in out if "reference:" in l)
    for name in over:
        assert ("'over': [" in text and name in text.split("'over': [")[1]
                .split("]")[0]), (name, text[-1500:])
    for name in ("same.index", "index.margin", "index.bad_rows"):
        assert all(name not in part.split("]")[0]
                   for part in text.split("'over': [")[1:]), name


def test_the_driver_says_when_the_accepted_driver_no_longer_reads_its_names(
        monkeypatch):
    """The sparse driver stands in for three names of a private copy of the
    accepted driver; where that driver drops one, loading fails loudly."""
    driver = harness.load_module(os.path.join(
        harness.BENCH_DIR, "drivers", "lm_serve_sparse_closed_loop.py"),
        "driver_sparse_under_test")
    assert driver.base.Workers is driver.Workers
    assert driver.base.reference_check is driver.reference_check
    monkeypatch.delattr(driver.base, "_shapes")
    with pytest.raises(harness.BenchError, match="_shapes"):
        driver._rebind()


def test_sparse_serve_traced_run_reads_the_new_counters(sparse_copy, capsys):
    rc, line, out = _run(capsys, "--workload", "tiny_longdoc", "--seed", "9",
                         "--seconds", "2", "--trace", "1")
    assert rc == 0 and line["correct"] is True, out[-12:]
    got = set(line["metrics"])
    assert {"selected_share.serve", "step_fill.serve", "prefill_share.serve",
            "cache_hit_share.serve", "sched_ms.serve",
            "token_gap_p50_ms.serve"} <= got
    # a request lasts about a window: 3 samples a run are no percentile
    assert not got & {"latency_p50_ms.serve", "latency_p95_ms.serve",
                      "ttft_p50_ms.serve"}
    assert 0 < line["metrics"]["selected_share.serve"]["value"] < 100
    assert not got & NEW_TRACE_METRICS     # no device plane on the CPU
    assert not got & {"mfu.serve", "mla_ms.serve"}    # not this cell's


def test_new_readers_are_silent_where_they_have_nothing_to_read(bench_copy):
    """On another cell's observations, or a program without the spans'
    new fields (the parent's), every new reader returns None."""
    cell = harness.Cell("tiny_serve", bench_copy)
    empty = {"trace": None, "spans": {}, "counters": {}, "registry": {},
             "shapes": {}, "peaks": {}, "window_s": 1.0, "cell": "x"}
    old_steps = [{"tokens": 4, "decode": 4, "prefill": 0, "prefill_start": 0,
                  "decode_context": 40, "expert_pairs": 3,
                  "experts_touched": 2, "sampled_rows": 4, "ms": 1.0}]
    parent = dict(empty, shapes={"kind": "lm_serve"}, counters={
        "window_steps": old_steps, "traced_steps": old_steps},
        trace={"window_s": 1.0, "devices": [{"modules": [], "ops": []}]})
    for name in sorted(NEW_TRACE_METRICS | {"selected_share.serve"}):
        reader = cell.layer_reader(name)
        assert reader.read(dict(empty)) is None, name
        assert reader.read(dict(parent)) is None, name


def test_roofline_arithmetic_at_the_published_widths():
    config = harness.load_json(os.path.join(
        harness.BENCH_DIR, "configs", "dots3_note_ep16_d9.json"))
    s = {"kind": "lm_serve", "attention": "selected+window", "hidden": 5120,
         "heads": 128, "q_lora_rank": 1024, "kv_lora_rank": 512,
         "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
         "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048,
         "gate": True, "swa": {"heads": 64, "q_lora_rank": 1024,
                               "kv_lora_rank": 1024, "qk_nope_head_dim": 192,
                               "qk_rope_head_dim": 64, "v_head_dim": 128,
                               "window": 513, "gate": True},
         "dense_intermediate": 13824, "moe_intermediate": 1536, "layers": 9,
         "moe_layers": 8, "full_layers": 3, "sliding_layers": 6,
         "n_routed_experts": 256, "experts_held": 16, "vocab": 19008}
    assert config["index_topk"] == 2048 and config["kv_lora_rank"] == 512
    # the sub-layers' parameters, as the configuration's file counts them
    assert roofline_dots3.projection_flops_per_token(s, s) / 2 == 134_021_120 \
        + 655_360
    assert roofline_dots3.indexer_flops_per_token(s) / 2 == 9_371_648
    assert roofline_dots3.projection_flops_per_token(s, s["swa"]) / 2 == (
        90_505_216 + 327_680)
    peaks = {"peak_tflops_bf16": 197.0, "hbm_gbps": 819.0}
    # a chunk of 2,048 queries against a prefix of 63,488: the issue's sums
    chunk = {"tokens": 2048, "decode": 0, "prefill": 2048,
             "prefill_start": 63488, "decode_context": 0, "expert_pairs": 0,
             "experts_touched": 0, "sampled_rows": 1,
             "index_pairs": 2048 * 63488 + 2048 * 2049 // 2,
             "selected_pairs": 2048 * 2048, "window_pairs": 2048 * 513,
             "dense_rows": 0}
    index = roofline_dots3.dsa_index_floor_s(s, chunk, peaks) / 3
    assert 0.010 < index < 0.012           # ~11 ms a full layer at peak
    attend = roofline_dots3.dsa_attend_floor_s(s, chunk, peaks) / 3
    assert 0.0059 < attend < 0.0075        # 1.17 TFLOP / 4.8 GB: the ridge
    assert roofline_dots3.swa_floor_s(s, chunk, peaks) > 0
    assert roofline_dots3.step_model_flops(s, chunk) > 2048 * 9 * 2e8
