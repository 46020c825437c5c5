"""Async input pipeline (data/pipeline.py + the loop's staged feed).

The pipeline's correctness contract is DETERMINISM: batch assembly is
counter-based (data/common.item_rng), so the multi-worker assembler must
yield bitwise-identical batches to the synchronous loop for any worker
count, and an interrupted+resumed consumer must see batch k unchanged.
The loop-level tests share ONE tiny trainer (module fixture) so the suite
pays a single train-step compile.
"""

import threading
import time

import numpy as np
import pytest

from mine_tpu.data import common
from mine_tpu.data.common import iterate_pair_batches
from mine_tpu.data.pipeline import DeviceStager, StagedBatch, prefetch


def _make_get_pair(num_items=23, fail_at=None, calls=None):
    """Fake loader honoring the collate contract; rng-dependent values so
    per-item PRNG misrouting shows up as a value diff, not just order."""
    def get_pair(index, rng=None):
        if calls is not None:
            calls.append(index)
        if fail_at is not None and index == fail_at:
            raise ValueError("boom at %d" % index)
        jitter = rng.uniform() if rng is not None else 0.0
        img = np.full((4, 4, 3), index + jitter, np.float32)
        side = {"img": img, "K": np.eye(3, dtype=np.float32),
                "xyzs": np.full((3, 5), index, np.float32)}
        tgt = dict(side)
        tgt["G_src_tgt"] = np.eye(4, dtype=np.float32)
        return side, tgt
    return get_pair


def _collect(**kw):
    kw.setdefault("num_items", 23)
    kw.setdefault("batch_size", 4)
    kw.setdefault("shuffle", True)
    kw.setdefault("seed", 3)
    kw.setdefault("epoch", 2)
    get_pair = kw.pop("get_pair", None) or _make_get_pair(kw["num_items"])
    return list(iterate_pair_batches(kw.pop("num_items"), get_pair, **kw))


def test_item_rng_is_counter_based():
    a = common.item_rng(1, 2, 3).uniform(size=4)
    b = common.item_rng(1, 2, 3).uniform(size=4)
    np.testing.assert_array_equal(a, b)
    # any key component moves the stream
    for other in [(0, 2, 3), (1, 0, 3), (1, 2, 4)]:
        assert not np.array_equal(a, common.item_rng(*other).uniform(size=4))


def test_assembler_matches_sequential():
    """N workers, any N, must reproduce the synchronous sequence bitwise —
    the property that makes checkpoint resume independent of the pipeline."""
    ref = _collect(workers=0)
    assert len(ref) == 5  # 23 items, batch 4, drop_last
    for workers in (1, 2, 5):
        got = _collect(workers=workers, prefetch_batches=2)
        assert len(got) == len(ref)
        for rb, gb in zip(ref, got):
            assert sorted(rb) == sorted(gb)
            for k in rb:
                np.testing.assert_array_equal(rb[k], gb[k])


def test_assembler_worker_error_propagates():
    """A single persistently-bad item no longer kills the epoch (bounded
    retry + quarantine, covered in test_chaos.py) — but a dataset where
    EVERY load fails still must fail loudly, on both feed paths."""
    def all_fail(index, rng=None):
        raise ValueError("boom at %d" % index)

    policy = common.get_retry_policy()
    common.set_retry_policy(common.RetryPolicy(max_item_retries=0,
                                               backoff_s=0.0))
    try:
        with pytest.raises(RuntimeError, match="every candidate"):
            _collect(get_pair=all_fail, shuffle=False, workers=3)
        # synchronous path raises the same error for the same data
        with pytest.raises(RuntimeError, match="every candidate"):
            _collect(get_pair=all_fail, shuffle=False, workers=0)
    finally:
        common.set_retry_policy(policy)
        common.PIPELINE_STATS.reset()


def test_assembler_shutdown_on_abandon():
    """Breaking out of the consumer must stop the worker pool (no leaked
    threads blocked on a full queue holding batch memory)."""
    def alive():
        return [t for t in threading.enumerate()
                if t.name.startswith("mine-tpu-assembler")]

    it = iterate_pair_batches(40, _make_get_pair(40), 4, True,
                              seed=0, epoch=0, workers=3)
    next(it)
    assert alive()
    it.close()
    deadline = time.time() + 5.0
    while alive() and time.time() < deadline:
        time.sleep(0.02)
    assert not alive()


def test_assembler_bounded_inflight():
    """At most max(workers, prefetch_batches) batches may be assembled
    ahead of the consumer (the credit semaphore's bound)."""
    calls = []
    it = iterate_pair_batches(64, _make_get_pair(64, calls=calls), 4, False,
                              seed=0, epoch=0, workers=2, prefetch_batches=3)
    next(it)
    time.sleep(0.3)  # give the pool time to run ahead if it were unbounded
    # consumed 1 batch -> at most (1 + bound) * batch_size items touched
    assert len(calls) <= (1 + 3) * 4
    it.close()


def test_exact_resume_mid_queue():
    """Kill the consumer mid-queue, rebuild the iterator (as a restored
    run does), skip k batches: batch k is bitwise what the uninterrupted
    sequence had — prefetched-but-unconsumed batches are not lost."""
    ref = _collect(workers=0)
    k = 2
    first = iterate_pair_batches(23, _make_get_pair(23), 4, True,
                                 seed=3, epoch=2, workers=3)
    for _ in range(k):
        next(first)
    first.close()  # abandon with batches still queued

    resumed = iterate_pair_batches(23, _make_get_pair(23), 4, True,
                                   seed=3, epoch=2, workers=3)
    for _ in range(k):
        next(resumed)
    batch_k = next(resumed)
    for key in ref[k]:
        np.testing.assert_array_equal(ref[k][key], batch_k[key])
    resumed.close()


# --------------------------------------------------------------------------
# cross-epoch lookahead: a chained epoch's pool builds the next epoch's first
# batches; the next open takes them only on an exact key match
# --------------------------------------------------------------------------

def _lookahead(name):
    from mine_tpu import telemetry
    return telemetry.counter("data.lookahead." + name).value


def _assembler_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("mine-tpu-assembler")]


def _assert_same(ref, got):
    assert len(ref) == len(got)
    for rb, gb in zip(ref, got):
        assert sorted(rb) == sorted(gb)
        for k in rb:
            np.testing.assert_array_equal(rb[k], gb[k])


def _epoch(get_pair, epoch, workers=3, pause=0.0, **kw):
    """One epoch through iterate_pair_batches, `pause` s a batch (a step)."""
    kw = dict(dict(num_items=23, batch_size=4, shuffle=True, seed=3), **kw)
    out = []
    for batch in iterate_pair_batches(kw.pop("num_items"), get_pair,
                                      kw.pop("batch_size"), kw.pop("shuffle"),
                                      epoch=epoch, workers=workers, **kw):
        out.append(batch)
        time.sleep(pause)
    return out


def test_chained_epochs_bitwise_and_taken():
    """Three epochs opened one after another equal the workers=0 sequence
    bitwise; the second chained open (epoch 2) takes what epoch 1's pool
    built ahead, the first (epoch 1) finds nothing."""
    get_pair = _make_get_pair(23, calls=[])   # a key no other test chains
    taken = [_lookahead("taken")]
    for e in range(3):
        got = _epoch(get_pair, e, pause=0.01)
        _assert_same(_epoch(get_pair, e, workers=0), got)
        taken.append(_lookahead("taken"))
        time.sleep(0.1)   # the loop's edge: the lookahead lands meanwhile
    assert taken[1] == taken[0] and taken[2] == taken[1]
    assert taken[3] > taken[2]


def test_chained_epochs_under_thread_stress():
    """More workers than cores, a switch interval of a microsecond: two
    pools claim from one epoch at its edge, and every slot of every epoch
    is still loaded exactly once, in the workers=0 bytes."""
    import os
    import sys

    streams = []
    base = _make_get_pair(23)

    def get_pair(index, rng=None):
        streams.append(rng.get_state()[1][0])
        return base(index, rng)

    workers = (os.cpu_count() or 8) + 2
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        got = [_epoch(get_pair, e, workers=workers, prefetch_batches=5)
               for e in range(4)]
    finally:
        sys.setswitchinterval(interval)
    for e, batches in enumerate(got):
        _assert_same(_epoch(base, e, workers=0), batches)
        slots = [common.item_rng(3, e, p).get_state()[1][0]
                 for p in range(20)]
        assert [streams.count(s) for s in slots] == [1] * 20, e


@pytest.mark.parametrize("change", ["seed", "batch_size", "epoch_plus_2",
                                    "dataset"])
def test_lookahead_dropped_on_any_other_key(change):
    """A next call with another seed, batch size, epoch + 2 or dataset takes
    nothing built ahead, counts it dropped and yields the workers=0 bytes."""
    get_pair = _make_get_pair(23, calls=[])
    for e in (0, 1):   # epoch 1 is chained: its pool builds epoch 2 ahead
        _epoch(get_pair, e)
    time.sleep(0.3)
    kw = {"seed": {"seed": 4}, "batch_size": {"batch_size": 3},
          "epoch_plus_2": {"epoch": 3}, "dataset": {}}[change]
    other = _make_get_pair(23, calls=[]) if change == "dataset" else get_pair
    epoch = kw.pop("epoch", 2)
    taken, dropped = _lookahead("taken"), _lookahead("dropped")
    got = _epoch(other, epoch, **kw)
    assert _lookahead("taken") == taken
    assert _lookahead("dropped") > dropped
    _assert_same(_epoch(other, epoch, workers=0, **kw), got)


@pytest.mark.parametrize("end", ["exhausted", "abandoned"])
def test_no_assembler_left_after_the_last_epoch(end):
    """No assembler thread outlives the last epoch's generator by 5 s, with
    lookahead work still in flight when it ends (run out or closed)."""
    def slow(index, rng=None, base=_make_get_pair(40)):
        time.sleep(0.02)
        return base(index, rng)

    _epoch(slow, 0, num_items=40)
    it = iterate_pair_batches(40, slow, 4, True, seed=3, epoch=1, workers=3)
    if end == "exhausted":
        list(it)
    else:
        for _ in range(9):   # every batch claimed: the lookahead is running
            next(it)
    it.close()
    deadline = time.time() + 5.0
    while _assembler_threads() and time.time() < deadline:
        time.sleep(0.02)
    assert not _assembler_threads()


def test_credit_bound_holds_across_the_edge():
    """With an epoch's last batch taken, at most max(workers,
    prefetch_batches) batches of the next epoch are built, and no more
    when the generator has run out."""
    calls = []
    get_pair = _make_get_pair(64, calls=calls)
    kw = dict(num_items=64, shuffle=False, workers=2, prefetch_batches=3)
    _epoch(get_pair, 0, **kw)
    it = iterate_pair_batches(64, get_pair, 4, False, seed=3, epoch=1,
                              workers=2, prefetch_batches=3)
    for _ in range(16):
        next(it)
    time.sleep(0.3)
    ahead = len(calls) - 2 * 64
    assert 0 < ahead <= 3 * 4
    assert next(it, None) is None
    time.sleep(0.3)
    assert len(calls) - 2 * 64 <= 3 * 4


def test_worker_killed_in_lookahead_loses_and_duplicates_nothing():
    """The sole worker dies (testing/faults.py kill) on the second item of
    epoch 2's first batch, which it builds ahead after epoch 1: the batch is
    handed back and built again, once, by the next open's pool."""
    from mine_tpu.testing import faults

    streams = []   # the item stream of every load: (epoch, slot) apart
    base = _make_get_pair(23)

    def get_pair(index, rng=None):
        streams.append(rng.get_state()[1][0])
        return base(index, rng)

    ref = [_epoch(get_pair, e, workers=0) for e in range(3)]
    del streams[:]
    # 20 loads an epoch: epoch 0, epoch 1, then epoch 2's batch 0 ahead
    faults.set_plan(faults.FaultPlan(kill_worker_at_call=42))
    got = []
    try:
        for e in range(3):
            got.append(_epoch(get_pair, e, workers=1))
            time.sleep(0.2)   # the lookahead runs (and dies) before the open
    finally:
        faults.set_plan(None)
    for r, g in zip(ref, got):
        _assert_same(r, g)
    # the one item loaded before the kill is loaded again with its batch
    # (epoch 2's pool builds epoch 3 ahead meanwhile: not counted)
    epoch2 = {common.item_rng(3, 2, p).get_state()[1][0] for p in range(20)}
    assert sum(s in epoch2 for s in streams) == 20 + 1


def test_device_stager_order_values_and_timing():
    import jax.numpy as jnp

    host = [{"x": np.full((2, 2), i, np.float32)} for i in range(6)]
    put = lambda b: {k: jnp.asarray(v) for k, v in b.items()}  # noqa: E731
    out = list(DeviceStager(iter(host), put, depth=2))
    assert len(out) == 6
    for i, sb in enumerate(out):
        assert isinstance(sb, StagedBatch)
        assert sb.h2d_ms >= 0.0
        np.testing.assert_array_equal(np.asarray(sb.batch["x"]), host[i]["x"])


def test_device_stager_propagates_put_errors():
    def bad_put(b):
        raise RuntimeError("transfer failed")
    with pytest.raises(RuntimeError, match="transfer failed"):
        list(DeviceStager(iter([{"x": np.zeros(2)}]), bad_put, depth=2))


def test_prefetch_reexport_from_loop():
    """loop.prefetch moved to data/pipeline.py; the re-export must keep the
    old import path working."""
    from mine_tpu.train import loop as loop_mod
    assert loop_mod.prefetch is prefetch
    assert list(loop_mod.prefetch(iter(range(5)))) == list(range(5))


# --------------------------------------------------------------------------
# loop-level: ONE shared tiny trainer (single train-step compile) drives the
# sync-vs-staged A/B and the breakdown-log test
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_loop_setup(tmp_path_factory):
    from mine_tpu.data.synthetic import SyntheticPairDataset
    from mine_tpu.train.loop import TrainLoop
    from mine_tpu.train.step import SynthesisTrainer
    from tests.test_train import tiny_config

    cfg = tiny_config(**{
        "data.img_h": 32, "data.img_w": 32,
        # donation on for BOTH feed paths: every batch is staged fresh, so
        # this also exercises donate_batch under the pipeline
        "training.donate_batch": True,
        "data.num_workers": 2,
        "training.log_interval": 1,
    })
    data = SyntheticPairDataset(num_views=5, num_points=16,
                                height=32, width=32, seed=0)
    trainer = SynthesisTrainer(cfg, steps_per_epoch=len(data))
    ws = str(tmp_path_factory.mktemp("pipeline_ws"))
    loop = TrainLoop(trainer, data, None, ws, logger=None, tb_writer=None)
    return trainer, loop


def _epoch_losses(trainer, loop, staged: bool):
    """Run one epoch; return the per-step loss sequence as float64."""
    from mine_tpu.utils import metrics_to_float

    loop.num_workers = 2 if staged else 0
    loop.staging_buffers = 2 if staged else 0
    recorded = []
    orig = trainer.train_step

    def recording_step(state, batch):
        state, metrics = orig(state, batch)
        recorded.append(metrics)
        return state, metrics

    trainer.train_step = recording_step
    try:
        state = trainer.init_state(batch_size=1, seed=0)
        loop.train_epoch(state, epoch=1)
    finally:
        trainer.train_step = orig
    return [metrics_to_float(m)["loss"] for m in recorded]


def test_staged_vs_sync_loss_sequences_identical(tiny_loop_setup):
    """The A/B the tentpole must win on semantics before speed: async
    assembly + double-buffered staging may not change a single loss."""
    trainer, loop = tiny_loop_setup
    sync_losses = _epoch_losses(trainer, loop, staged=False)
    staged_losses = _epoch_losses(trainer, loop, staged=True)
    assert len(sync_losses) == 4  # 4 pairs, batch 1
    assert sync_losses == staged_losses
    assert all(np.isfinite(v) for v in sync_losses)


class _ListLogger:
    def __init__(self):
        self.lines = []

    def info(self, msg, *args):
        self.lines.append(msg % args if args else str(msg))


def test_loop_logs_parseable_breakdown(tiny_loop_setup):
    """Every log interval must carry the host_wait/device/h2d split, in the
    exact format tools/step_breakdown.py parses."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        import step_breakdown
    finally:
        sys.path.pop(0)

    trainer, loop = tiny_loop_setup
    loop.num_workers = 2
    loop.staging_buffers = 2
    logger = _ListLogger()
    loop.logger = logger
    try:
        state = trainer.init_state(batch_size=1, seed=0)
        loop.train_epoch(state, epoch=1)
    finally:
        loop.logger = None

    samples = step_breakdown.parse_lines(logger.lines)
    assert len(samples["step"]) == 4  # log_interval=1, 4 steps
    for k in ("step", "host_wait", "device", "h2d"):
        assert all(v >= 0.0 for v in samples[k]), k
    # the loop's invariant: device = step - host_wait (clamped at 0)
    for s, hw, dv in zip(samples["step"], samples["host_wait"],
                         samples["device"]):
        np.testing.assert_allclose(dv, max(0.0, s - hw), atol=0.1)
    # meters carry the same averages for the epoch summary
    assert loop.time_meters["step_ms"].count == 4
    summary = step_breakdown.summarize(samples)
    assert "host-bound fraction" in summary
