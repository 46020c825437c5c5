"""Shared batching machinery for all dataset loaders.

One implementation of shuffle -> host-shard -> collate (the reference's
DistributedSampler + DataLoader + collate + set_data L=1 squeeze,
train.py:83-87, synthesis_task.py:184-209) used by the LLFF, RealEstate10K,
and synthetic loaders, so the semantics (shuffle the GLOBAL index list with
the epoch-seeded RNG, then stride-shard across hosts — DistributedSampler
order) cannot drift between them.

Batch assembly is COUNTER-BASED: every item draws from its own PRNG stream
keyed by (seed, epoch, position-in-shard-order), so batch b is a pure
function of (dataset, seed, epoch, b). That makes the sequence independent
of who assembles it — the sequential loop below and the multi-worker
threaded assembler (mine_tpu.data.pipeline) produce bitwise-identical
batches, and an interrupted run reproduces batch k exactly on resume.
(The pre-pipeline implementation threaded ONE RandomState through all
items in consumption order, which serializes assembly by construction.)
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Callable, Dict, Iterator, List, Tuple

import numpy as np

from mine_tpu import telemetry
from mine_tpu.testing import faults


# ---------------- degradation policy + counters ----------------

@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded per-item retry (data.max_item_retries /
    data.item_retry_backoff): a transient decode/IO failure is retried
    with a fresh-but-identical PRNG stream (so a healed retry yields the
    exact bytes an unfailed load would have), then the item is quarantined
    and deterministically replaced."""
    max_item_retries: int = 2
    backoff_s: float = 0.05


_retry_policy = RetryPolicy()


def set_retry_policy(policy: RetryPolicy):
    global _retry_policy
    _retry_policy = policy


def get_retry_policy() -> RetryPolicy:
    return _retry_policy


class _PipelineStats:
    """Process-wide data-degradation counters, surfaced through the train
    loop's step-time log line (`data_errors`). Thread-safe: assembler
    workers bump them concurrently."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with self._lock:
            self.data_errors = 0       # failed item-load attempts
            self.quarantined = set()   # dataset indices proven persistently bad
            self.worker_respawns = 0

    def record_error(self, n: int = 1):
        with self._lock:
            self.data_errors += n
        telemetry.counter("data.errors").inc(n)

    def record_quarantine(self, index: int):
        with self._lock:
            new = int(index) not in self.quarantined
            self.quarantined.add(int(index))
        if new:
            telemetry.counter("data.quarantined").inc()
            telemetry.emit("data.quarantine", index=int(index))

    def is_quarantined(self, index: int) -> bool:
        with self._lock:
            return int(index) in self.quarantined

    def record_respawn(self):
        with self._lock:
            self.worker_respawns += 1
        telemetry.counter("data.worker_respawns").inc()

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {"data_errors": self.data_errors,
                    "quarantined": len(self.quarantined),
                    "worker_respawns": self.worker_respawns}


PIPELINE_STATS = _PipelineStats()


def _mix64(x: int) -> int:
    """splitmix64 finalizer — decorrelates nearby (seed, epoch, position)
    keys into independent-looking 64-bit values."""
    mask = (1 << 64) - 1
    x = (x + 0x9E3779B97F4A7C15) & mask
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & mask
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & mask
    return x ^ (x >> 31)


def item_rng(seed: int, epoch: int, position: int) -> np.random.RandomState:
    """The PRNG stream of one item slot.

    `position` is the index into the host's shard order (NOT the dataset
    index): two epochs sampling the same item get different streams, and
    the stream does not depend on worker count or consumption order.
    """
    key = _mix64(((int(seed) + 1) << 40)
                 ^ ((int(epoch) + 1) << 20)
                 ^ int(position))
    return np.random.RandomState(key % (1 << 32))


def shard_order(num_items: int, shuffle: bool, seed: int, epoch: int,
                shard_index: int, num_shards: int) -> np.ndarray:
    """This host's item order: epoch-seeded global shuffle, then stride-shard
    (DistributedSampler semantics)."""
    order = np.arange(num_items)
    if shuffle:
        np.random.RandomState(seed + epoch).shuffle(order)
    return order[shard_index::num_shards]


def num_batches(num_items: int, batch_size: int, drop_last: bool) -> int:
    if drop_last:
        return num_items // batch_size
    return -(-num_items // batch_size)


def load_item(get_pair: Callable[[int, np.random.RandomState],
                                 Tuple[Dict, Dict]],
              order: np.ndarray,
              position: int,
              seed: int,
              epoch: int) -> Tuple[Dict, Dict]:
    """Load shard-order slot `position` with bounded retry, then
    deterministic quarantine-and-replace.

    Retries rebuild item_rng from scratch each attempt, so a transient
    failure that heals produces bytes identical to a run that never
    failed. A persistently-bad item (all retries exhausted) is quarantined
    and replaced by the next non-bad dataset index in shard order —
    `order[(position + k) % len(order)]`, probed with the SAME rng stream
    (still keyed to the original position): the replacement depends only
    on (order, position) and which items are persistently bad, never on
    worker count or assembly timing, so batches stay bitwise-deterministic.
    The quarantine set is a cost memo (skip the doomed retries when the
    same index comes around again), not an input to the result.
    """
    policy = _retry_policy
    n = len(order)
    last_err: Exception = None
    for k in range(n):
        idx = int(order[(position + k) % n])
        if k > 0 and PIPELINE_STATS.is_quarantined(idx):
            continue
        for attempt in range(policy.max_item_retries + 1):
            try:
                faults.on_item_load(idx)
                pair = get_pair(idx, item_rng(seed, epoch, position))
            except Exception as e:
                last_err = e
                PIPELINE_STATS.record_error()
                if attempt < policy.max_item_retries:
                    time.sleep(policy.backoff_s * (2 ** attempt))
                continue
            if k > 0:
                logging.getLogger(__name__).warning(
                    "item %d (slot %d) quarantined after %d attempts — "
                    "substituting item %d: %s", int(order[position]),
                    position, policy.max_item_retries + 1, idx, last_err)
            return pair
        PIPELINE_STATS.record_quarantine(idx)
    raise RuntimeError(
        f"every candidate item for slot {position} failed "
        f"(dataset unusable); last error: {last_err!r}") from last_err


def assemble_batch(get_pair: Callable[[int, np.random.RandomState],
                                      Tuple[Dict, Dict]],
                   order: np.ndarray,
                   batch_index: int,
                   batch_size: int,
                   seed: int,
                   epoch: int,
                   collate=None) -> Dict[str, np.ndarray]:
    """Assemble + collate batch `batch_index` of the shard order (`collate`:
    the loader's own, for items that are no (src, tgt) pairs).

    Pure in (order, batch_index, seed, epoch): any worker can build any
    batch, in any order, and get the same bytes. Item loads go through
    `load_item` (bounded retry + deterministic quarantine), so one bad
    example degrades the batch, not the epoch.
    """
    lo = batch_index * batch_size
    idxs = order[lo:lo + batch_size]
    pairs = [load_item(get_pair, order, lo + j, seed, epoch)
             for j in range(len(idxs))]
    return (collate or collate_pairs)(pairs)


def iterate_pair_batches(num_items: int,
                         get_pair: Callable[[int, np.random.RandomState],
                                            Tuple[Dict, Dict]],
                         batch_size: int,
                         shuffle: bool,
                         seed: int = 0,
                         epoch: int = 0,
                         drop_last: bool = True,
                         shard_index: int = 0,
                         num_shards: int = 1,
                         workers: int = 0,
                         prefetch_batches: int = 2,
                         collate=None
                         ) -> Iterator[Dict[str, np.ndarray]]:
    """Yield collated framework batches of (src, tgt) item pairs.

    workers=0: assemble on the calling thread (the original synchronous
    path). workers>0: delegate to the threaded assembler
    (mine_tpu.data.pipeline.threaded_pair_batches) — same batch sequence,
    assembled by a worker pool with at most ~max(workers, prefetch_batches)
    batches in flight.
    """
    if workers > 0:
        from mine_tpu.data.pipeline import threaded_pair_batches
        yield from threaded_pair_batches(
            num_items, get_pair, batch_size, shuffle, seed=seed, epoch=epoch,
            drop_last=drop_last, shard_index=shard_index,
            num_shards=num_shards, workers=workers,
            prefetch_batches=prefetch_batches, collate=collate)
        return
    order = shard_order(num_items, shuffle, seed, epoch, shard_index,
                        num_shards)
    for b in range(num_batches(len(order), batch_size, drop_last)):
        yield assemble_batch(get_pair, order, b, batch_size, seed, epoch,
                             collate=collate)


def collate_pairs(pairs) -> Dict[str, np.ndarray]:
    """(src, tgt) item dicts -> the framework batch contract (NHWC images,
    [B,3,3] intrinsics, [B,4,4] src<-tgt pose, [B,3,N] camera-frame points)."""
    return {
        "src_img": np.stack([s["img"] for s, _ in pairs]),
        "tgt_img": np.stack([t["img"] for _, t in pairs]),
        "K_src": np.stack([s["K"] for s, _ in pairs]),
        "K_tgt": np.stack([t["K"] for _, t in pairs]),
        "G_src_tgt": np.stack([t["G_src_tgt"] for _, t in pairs]),
        "pt3d_src": np.stack([s["xyzs"] for s, _ in pairs]),
        "pt3d_tgt": np.stack([t["xyzs"] for _, t in pairs]),
    }
