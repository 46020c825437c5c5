"""The looped language model's driver end to end on the CPU at a small size,
through the test-only seam of conftest.py: result keys, `correct`, the
reference check, the counters' metric, and no device metric in a rehearsal.
The cell arrives as new files and entries; `bench_copy` asserts on the way
out that no file that was there was edited."""

import json
import os

import pytest

from benchmark import harness, run

from conftest import _write  # noqa: E402  (the fixtures' own helper)

TINY_LM = {"lm.hidden_size": 64, "lm.num_attention_heads": 4,
           "lm.num_key_value_heads": 4, "lm.head_dim": 16,
           "lm.intermediate_size": 160, "lm.vocab_size": 512,
           "lm.num_hidden_layers": 2, "data.seq_len": 64,
           "data.per_gpu_batch_size": 2, "training.log_interval": 2,
           "lr.lm_lr": 0.003}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
LM_METRICS = ("attention_ms.train", "mlp_ms.train", "head_loss_ms.train",
              "attention_roofline.train", "mfu.train", "pack_fill.train")


@pytest.fixture
def lm_copy(bench_copy):
    """`bench_copy` plus a tiny cell of the new configuration's kind."""
    bdir = os.path.join(bench_copy, "benchmark")
    _write(os.path.join(bdir, "configs", "tiny_lm.json"), {
        "name": "tiny_lm", "source": "test only",
        "yaml": "mine_tpu/configs/params_ouro_2p6b.yaml",
        "overrides": TINY_LM, "reduced": sorted(TINY_LM),
        "as_run": {"lm.total_ut_steps": 4, "model.family": "looplm"}})
    _write(os.path.join(bdir, "traffic", "tiny_packed.json"), {
        "driver": "lm_train_loop",
        "dataset": {"num_rows": 8, "doc_len_median": 20, "doc_len_min": 4},
        "warmup": {"steps_before_epoch_end": 1, "steps_after_epoch_start": 1},
        "trace_seconds": 0.5})
    path = os.path.join(bench_copy, "BENCHMARK.json")
    manifest = harness.load_json(path)
    manifest["configs"].append({
        "name": "tiny_lm", "source": "test only",
        "file": "benchmark/configs/tiny_lm.json",
        "reduced": sorted(TINY_LM), "why": "CPU rehearsal"})
    manifest["workloads"].append({
        "name": "tiny_lm_train", "config": "tiny_lm",
        "traffic": "tiny_packed", "chips": 1, "why": "CPU rehearsal"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "ouro_train_packed4k" in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["tiny_lm_train"]
    _write(path, manifest)
    return bench_copy


def _run(capsys, *argv):
    rc = run.run(list(argv))
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_lm_end_to_end_run(lm_copy, capsys):
    rc, line, out = _run(capsys, "--workload", "tiny_lm_train", "--seed",
                         str(2**31 + 4321), "--seconds", "2", "--trace", "0")
    assert rc == 0 and set(line) == RESULT_KEYS
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True, out[-12:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    text = "\n".join(out)
    # the reference check ran, on every quantity it names, and passed
    assert "reference check in" in text and "'ok': True" in text
    from benchmark.drivers import lm_train_loop
    for name in lm_train_loop.TOLERANCES:
        assert "'%s'" % name in text, name
    assert "'reference': True" in text and "tokens_per_s" in text


def test_lm_traced_run_prints_counters_and_no_device_metric(lm_copy, capsys):
    rc, line, out = _run(capsys, "--workload", "tiny_lm_train", "--seed", "9",
                         "--seconds", "2", "--trace", "1")
    assert rc == 0 and line["correct"] is True, out[-12:]
    got = set(line["metrics"])
    assert {"pack_fill.train", "feed_wait_ms.train", "feed_starved_ms.train",
            "step_dispatch_ms.train", "host_batch_ms.train"} <= got, got
    assert 99.0 <= line["metrics"]["pack_fill.train"]["value"] <= 100.0
    # no device plane on the CPU: none of its metrics prints
    assert not got & {"attention_ms.train", "mlp_ms.train", "mfu.train",
                      "head_loss_ms.train", "attention_roofline.train",
                      "step_device_ms.train", "optimizer_ms.train"}
    assert not any("roofline" in k or "device" in k or "pallas" in k
                   or "mfu" in k for k in got)
    assert "breakdown" not in line and "setup_s" not in line["metrics"]


def test_new_readers_are_silent_on_another_family(bench_copy, capsys):
    """conftest.py appends its tiny MINE cell to every listed `.train`
    metric, the new ones too: each new reader returns nothing there."""
    rc, line, out = _run(capsys, "--workload", "tiny_train", "--seed", "7",
                         "--seconds", "2", "--trace", "1")
    assert rc == 0 and line["correct"] is True, out[-12:]
    assert not set(LM_METRICS) & set(line["metrics"])


def test_planted_faults_fail_the_reference_check(lm_copy):
    """`compare` on what `observe_first_step` takes round the trainer's own
    compiled step: the program as shipped passes; a state the step left
    unchanged, half of the batch dropped inside the step, and an update at
    twice the stated rate each fail, by the limits the cell runs under."""
    import jax
    import numpy as np
    cell = harness.Cell("tiny_lm_train")
    drv = cell.driver()
    config, dataset, trainer, state = drv.build_program(cell, 2**31 + 11)
    batch = next(dataset.batch_iterator(trainer.global_batch_size(),
                                        shuffle=True, seed=3, epoch=1))
    ids = drv._frequent_ids(batch)
    lm = jax.device_get(state.params["lm"])
    want = drv.reference_numbers(lm, batch, config)

    def read(state, batch, step=trainer.train_step, want=want):
        _, metrics, observed = drv.observe_first_step(
            trainer, state, trainer.put_batch(batch), ids, step)
        want = dict(want, **drv.same_operand_numbers(
            lm["head"], observed["forward"], config))
        return observed, metrics, drv.compare(
            drv.program_numbers(observed, jax.device_get(metrics)), want)

    fresh = lambda: trainer.init_state(2, seed=harness.mix_seed(  # noqa: E731
        2**31 + 11, "weights"))
    observed, metrics, shipped = read(state, batch)
    assert shipped["ok"] and not shipped["over"], shipped

    # the step returned the state it was given
    unchanged = drv.compare(drv.program_numbers(
        dict(observed, after=observed["before"]), jax.device_get(metrics)),
        dict(want, **drv.same_operand_numbers(lm["head"],
                                              observed["forward"], config)))
    assert {k for k in drv.TOLERANCES if k.startswith("delta.")} == set(
        unchanged["over"]), unchanged
    assert all(unchanged["errors"][k] == 1.0 for k in unchanged["over"])

    # the step saw the first row only: the second row's slots masked out
    mask = np.array(batch["mask"])
    mask[1:] = 0
    half = read(fresh(), dict(batch, mask=mask))[2]
    assert "step.grad_norm" in half["over"], half
    assert any(k.startswith("delta.") for k in half["over"]), half

    # the reference steps at half the program's rate
    slow = dict(want)
    for k in want:
        if k.startswith("delta."):
            slow[k] = [0.5 * np.asarray(part) for part in want[k]]
    fast = read(fresh(), batch, want=slow)[2]
    assert {k for k in drv.TOLERANCES if k.startswith("delta.")} <= set(
        fast["over"]), fast
