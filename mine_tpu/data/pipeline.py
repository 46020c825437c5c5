"""Asynchronous input pipeline: host-side batch assembly + device staging.

Closes the real-loop vs device-step gap measured in the round-5 soak
(train_cli ~0.8 s/step vs bench's 0.22 s jitted step): the host-side feed —
item decode/sampling, collate, and a single blocking `device_put` on the
critical path — left the chip idle most of the wall-clock. Three layers,
each independently knobbed:

  1. `threaded_pair_batches` — a multi-worker batch assembler over the
     data/common.py batching core. Determinism is free because batch
     assembly is counter-based (common.item_rng): batch b is a pure
     function of (seed, epoch, b), so N workers building batches out of
     order still yield the exact sequence the synchronous loop yields,
     and checkpoint resume reproduces batch k bitwise.
  2. `prefetch` — a single background producer thread with a bounded
     queue (for iterators with no parallelizable structure, e.g. a
     custom batch_iterator that does not go through the common core).
  3. `DeviceStager` — double-buffered host->device staging: a background
     thread runs the sharding-aware transfer (`put_fn`, typically
     SynthesisTrainer.put_batch) and keeps `depth` device-resident
     batches in flight, so the H2D copy of batch k+1 overlaps the device
     compute of step k. Each staged batch carries its measured `h2d_ms`
     for the train loop's step-time breakdown.

Worker threads (not processes): the assembly work is numpy slicing/stacking
and (for real loaders) libmtio/PIL decodes that release the GIL, and the
main thread spends its step time blocked in the JAX runtime — also outside
the GIL — so threads overlap where it matters without process-spawn or
pickling costs.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Dict, Iterator, NamedTuple

import numpy as np

from mine_tpu import telemetry
from mine_tpu.data import common

_END = object()


def prefetch(iterator: Iterator, depth: int = 2,
             name: str = "host") -> Iterator:
    """Background-thread prefetch: overlaps producing `iterator`'s items
    with whatever the consumer does between `next()` calls. `name` is the
    stage's: a consumer that finds the queue empty waits inside a
    `data.<name>.starved` span (telemetry/spans.py).

    Abandoning the generator (consumer raised / broke out) stops the
    producer promptly instead of leaving a thread blocked on a full queue
    holding batch memory. Producer exceptions re-raise on the consumer.
    """
    q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
    stop = threading.Event()
    err = []

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def producer():
        try:
            for item in iterator:
                if not _put(item):
                    return
        except BaseException as e:  # surface loader errors on the consumer
            err.append(e)
        finally:
            _put(_END)

    t = threading.Thread(target=producer, daemon=True,
                         name="mine-tpu-prefetch")
    t.start()
    starved = "data.%s.starved" % name
    try:
        while True:
            try:
                item = q.get_nowait()
            except queue.Empty:
                with telemetry.span(starved):
                    item = q.get()
            if item is _END:
                if err:
                    raise err[0]
                return
            yield item
    finally:
        stop.set()


def threaded_pair_batches(num_items: int,
                          get_pair,
                          batch_size: int,
                          shuffle: bool,
                          seed: int = 0,
                          epoch: int = 0,
                          drop_last: bool = True,
                          shard_index: int = 0,
                          num_shards: int = 1,
                          workers: int = 2,
                          prefetch_batches: int = 2,
                          collate=None
                          ) -> Iterator[Dict[str, np.ndarray]]:
    """Multi-worker batch assembly, yielded strictly in batch order.

    Same arguments and same batch sequence as
    common.iterate_pair_batches(workers=0); the pool only changes WHO
    assembles each batch. At most max(workers, prefetch_batches) batches
    are held assembled-but-unconsumed (bounded memory), enforced by a
    credit semaphore the consumer refills. A worker exception is re-raised
    on the consumer at the failing batch's position; abandoning the
    generator stops the pool promptly.

    A worker that DIES (thread killed by a non-Exception, e.g. the chaos
    suite's WorkerKill) does not end the epoch: its claimed batch is
    requeued for the surviving workers, and when the whole pool is dead
    the consumer respawns it (bounded budget, counted in
    common.PIPELINE_STATS.worker_respawns) instead of raising.
    """
    # what an epoch's edge costs: from this generator's first step (order,
    # pool start) until its first batch is ready
    opening = telemetry.span("data.iterator.open", epoch=epoch,
                             workers=workers)
    opening.__enter__()
    order = common.shard_order(num_items, shuffle, seed, epoch, shard_index,
                               num_shards)
    nb = common.num_batches(len(order), batch_size, drop_last)

    pool_size = max(1, workers)
    credits = threading.Semaphore(max(workers, prefetch_batches, 1))
    cv = threading.Condition()
    results: Dict[int, Dict] = {}
    errors = []
    requeue = []  # batch indices whose claiming worker died mid-assembly
    next_batch = [0]  # next index to hand to a worker
    stop = threading.Event()

    def worker():
        while not stop.is_set():
            if not credits.acquire(timeout=0.1):
                continue
            with cv:
                if errors or (next_batch[0] >= nb and not requeue):
                    credits.release()
                    return
                if requeue:
                    b = requeue.pop()
                else:
                    b = next_batch[0]
                    next_batch[0] += 1
            try:
                with telemetry.span("data.assemble.batch", batch=b):
                    batch = common.assemble_batch(get_pair, order, b,
                                                  batch_size, seed, epoch,
                                                  collate=collate)
            except Exception as e:
                with cv:
                    errors.append((b, e))
                    cv.notify_all()
                return
            except BaseException:
                # the thread is dying (injected kill / interpreter teardown):
                # hand the claimed batch back so the pool can finish it
                with cv:
                    requeue.append(b)
                    cv.notify_all()
                credits.release()
                return
            with cv:
                results[b] = batch
                cv.notify_all()

    def spawn(i):
        t = threading.Thread(target=worker, daemon=True,
                             name="mine-tpu-assembler-%d" % i)
        t.start()
        return t

    threads = [spawn(i) for i in range(pool_size)]
    # a dead pool is respawned rather than fatal, but boundedly — a pool
    # that keeps dying (systemic failure, not one bad worker) must still
    # surface instead of flapping forever
    respawn_budget = 3 * pool_size
    try:
        for b in range(nb):
            with cv:
                while b not in results:
                    # fail at the EARLIEST failing batch position so the
                    # consumer sees errors in sequence order
                    pending_err = [e for eb, e in errors if eb <= b]
                    if pending_err:
                        raise pending_err[0]
                    if not any(t.is_alive() for t in threads) \
                            and b not in results:
                        if respawn_budget > 0 and not errors:
                            respawn_budget -= 1
                            common.PIPELINE_STATS.record_respawn()
                            threads = [t for t in threads if t.is_alive()]
                            threads.append(spawn(3 * pool_size
                                                 - respawn_budget))
                            continue
                        raise RuntimeError(
                            "assembler workers died without producing "
                            "batch %d" % b)
                    cv.wait(0.1)
                batch = results.pop(b)
            if opening is not None:
                opening.__exit__(None, None, None)
                opening = None
            yield batch
            credits.release()
    finally:
        if opening is not None:  # closed or failed before a first batch
            opening.__exit__(None, None, None)
        stop.set()
        with cv:
            cv.notify_all()


class StagedBatch(NamedTuple):
    """A device-resident batch plus the measured host->device copy time."""
    batch: Dict
    h2d_ms: float


class DeviceStager:
    """Double-buffered host->device staging.

    A background thread pulls host batches from `host_batches`, runs the
    sharding-aware transfer `put_fn` (e.g. SynthesisTrainer.put_batch —
    `jax.device_put` with the mesh's input sharding), blocks until the
    copy lands (in the BACKGROUND thread — the consumer never waits on a
    copy that finished overlapped), and enqueues up to `depth` staged
    batches. depth>=2 gives the double buffer: while the device computes
    step k on buffer A, the copy of batch k+1 fills buffer B.

    Iterating yields StagedBatch(batch, h2d_ms). Producer exceptions
    re-raise on the consumer; abandoning the iterator stops the thread.
    """

    def __init__(self, host_batches: Iterator[Dict],
                 put_fn: Callable[[Dict], Dict],
                 depth: int = 2):
        self.depth = max(1, int(depth))
        self._host_batches = host_batches
        self._put_fn = put_fn

    def __iter__(self) -> Iterator[StagedBatch]:
        def stage():
            import jax
            for np_batch in self._host_batches:
                with telemetry.span("data.stage.h2d") as h2d:
                    dev = self._put_fn(np_batch)
                    jax.block_until_ready(dev)
                yield StagedBatch(dev, h2d.ms)

        return prefetch(stage(), depth=self.depth, name="stage")
