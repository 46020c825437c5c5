"""Micro-batcher: coalesce pending view requests into one device call.

Serving traffic arrives as independent (image_id, pose) requests, usually
against DIFFERENT cached MPIs. Dispatching each alone wastes the batch axis;
this batcher holds a request up to `max_wait_ms`, coalesces everything
pending (across distinct entries — the engine's request-gather handles the
mapping) and flushes one `RenderEngine.render_many` call of at most
`max_requests`. Results come back through per-request futures.

Thread model: callers `submit` from any thread; a single daemon flush thread
owns the device dispatch, so the engine's jitted call never races. Tests
drive `flush()` directly with `start=False` (no timing dependence).

Observability: the flush thread's time is covered by three spans
(telemetry/spans.py) — `serve.batcher.idle` (empty queue),
`serve.batcher.linger` (waiting for co-riders) and `serve.batcher.flush`
(one dispatch: the engine's spans and `serve.batcher.deliver` are its
children) — and each request's time in the queue is one pre-measured
`serve.batcher.queue_wait` record whose parent is the flush that released
it. A request carrying a TraceContext (telemetry/tracing.py — attached by
`ServeFleet.submit`, or started here when sampling is on) rides the pending
tuple across the thread handoff; the queue-wait record forwards itself to
it as its "queue" span (tagged with which trigger released the batch: a
full bucket or the deadline), the engine's spans as pad/render/encode, and
the trace is sealed when the future resolves.
An attached `slo` tracker (telemetry/slo.py) sees EVERY request's
end-to-end latency — SLO accounting is never sampled.

Self-protection (PR 11, serve/admission.py): requests carry a priority
`tier` and an optional deadline. An attached `AdmissionController` is
consulted at submit time under the queue lock — a shed verdict resolves the
future immediately with `RequestShed`; a degrade verdict tags the request
for the graceful ladder (stepped-down cache quant on a sync-encode miss,
and an all-degraded batch caps at half the pose bucket). The flush path
runs a DEADLINE SWEEP before selecting: already-expired requests are purged
(future gets `DeadlineExceeded`) and never rendered. Dispatch selection is
priority-ordered — highest tier first, FIFO within a tier — via a stable
sort, so with every request at the default tier the order (and therefore
the output) is bitwise-identical to the plain FIFO batcher.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future
from typing import List, NamedTuple, Optional

import numpy as np

from mine_tpu import telemetry
from mine_tpu.analysis.locks import ordered_condition
from mine_tpu.serve.admission import (AdmissionController, DeadlineExceeded,
                                      RequestShed)
from mine_tpu.serve.engine import RenderEngine, pow2_bucket
from mine_tpu.telemetry import tracing
from mine_tpu.telemetry.slo import SLOTracker

_log = logging.getLogger(__name__)


class _Pending(NamedTuple):
    """One queued request. Field ORDER is part of the queue's informal API
    (tests probe `_pending[0][3]` for the enqueue timestamp): the first
    five fields are exactly the PR-5 tuple; the tail is the PR-11
    resilience state."""
    image_id: str
    pose: np.ndarray
    fut: Future
    t_enq: float
    trace: Optional[tracing.TraceContext]
    tier: int = 1
    deadline: Optional[float] = None  # perf_counter timestamp; None = none
    degraded: bool = False
    image: Optional[np.ndarray] = None  # sync-encode fallback pixels


class MicroBatcher:
    def __init__(self, engine: RenderEngine,
                 max_requests: int = 8,
                 max_wait_ms: float = 2.0,
                 start: bool = True,
                 slo: Optional[SLOTracker] = None,
                 auto_trace: bool = True,
                 admission: Optional[AdmissionController] = None,
                 default_tier: int = 1,
                 request_deadline_ms: float = 0.0):
        if max_requests < 1:
            raise ValueError(f"max_requests must be >= 1, got {max_requests}")
        self.engine = engine
        self.max_requests = int(max_requests)
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.flushes = 0
        self.slo = slo
        # the fleet's submit makes the sampling decision (its trace carries
        # the route span) and passes the result down — auto_trace=False
        # there keeps this layer from re-rolling the dice on requests the
        # fleet already declined to sample
        self.auto_trace = auto_trace
        # self-protection (serve/admission.py): None = every request admits
        # unconditionally (the PR-10 behavior, bitwise)
        self.admission = admission
        self.default_tier = int(default_tier)
        self.request_deadline_ms = float(request_deadline_ms)
        self.expired = 0  # requests purged by the deadline sweep
        # injectable clock (instance attr): the deadline-sweep regression
        # test replaces it with a fake so expiry needs no real waiting
        self._now = time.perf_counter
        self._cv = ordered_condition("serve.batcher.cv")
        # queued-but-unresolved + dispatched-but-unresolved: the in-flight
        # pressure signal the admission controller consumes (guarded by cv)
        self._inflight = 0
        self._pending: List[_Pending] = []
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        if start:
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name="mine-tpu-serve-batcher")
            self._thread.start()

    def submit(self, image_id: str, pose_44: np.ndarray,
               trace: Optional[tracing.TraceContext] = None,
               tier: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               image: Optional[np.ndarray] = None) -> Future:
        """Enqueue one view request; resolves to (rgb [3,H,W],
        depth [1,H,W]) f32 numpy. `trace` attaches an already-started
        request trace (the fleet's submit passes one that already carries
        the route span); without one, the batcher makes its own sampling
        decision (unless auto_trace is off) so a bare-batcher deployment
        still gets traces.

        `tier` is the request's priority class (default `default_tier`;
        serve/admission.py); under pressure an attached controller may
        resolve the future immediately with `RequestShed`, or tag the
        request degraded. `deadline_ms` bounds its total queue+render time
        (default `request_deadline_ms`; 0/None = no deadline): a request
        still queued past its deadline is purged at dispatch time with
        `DeadlineExceeded`. `image` optionally carries the source pixels so
        a cache miss can fall back to the synchronous encode."""
        if trace is None and self.auto_trace:
            trace = tracing.start("serve.request", image_id=str(image_id)[:12])
        tier = self.default_tier if tier is None else int(tier)
        if deadline_ms is None:
            deadline_ms = self.request_deadline_ms
        fut: Future = Future()
        decision = "admit"
        with self._cv:
            if self._closed:
                raise RuntimeError("MicroBatcher is closed")
            if self.admission is not None:
                decision = self.admission.decide(
                    tier, len(self._pending), self._inflight)
            if decision != "shed":
                now = self._now()
                self._pending.append(_Pending(
                    image_id, np.asarray(pose_44, np.float32), fut, now,
                    trace, tier,
                    now + deadline_ms / 1e3 if deadline_ms > 0 else None,
                    decision == "degrade", image))
                self._inflight += 1
                self._cv.notify()
        if decision == "shed":
            fut.set_exception(RequestShed(
                f"request for {str(image_id)[:12]} shed at tier {tier} "
                f"(admission state {self.admission.state})"))
            tracing.finish(trace, ok=False)
        return fut

    def _take_batch(self, now: float):
        """Select the next dispatch batch (callers hold self._cv); returns
        (batch, expired). The sweep purges already-expired requests FIRST —
        they are never rendered; selection is then highest-tier-first, FIFO
        within a tier (a STABLE sort: uniform tiers reproduce plain FIFO
        exactly); an all-degraded batch caps at half the pose bucket (the
        graceful ladder's smaller-bucket step)."""
        expired: List[_Pending] = []
        if any(r.deadline is not None and r.deadline <= now
               for r in self._pending):
            keep: List[_Pending] = []
            for r in self._pending:
                (expired if r.deadline is not None and r.deadline <= now
                 else keep).append(r)
            self._pending[:] = keep
        if len({r.tier for r in self._pending}) > 1:
            ranked = sorted(self._pending, key=lambda r: (-r.tier, r.t_enq))
            batch = ranked[:self.max_requests]
            taken = {id(r) for r in batch}
            self._pending[:] = [r for r in self._pending
                                if id(r) not in taken]
        else:
            batch = self._pending[:self.max_requests]
            del self._pending[:len(batch)]
        if batch and all(r.degraded for r in batch):
            cap = max(1, self.max_requests // 2)
            if len(batch) > cap:
                self._pending[:0] = batch[cap:]
                batch = batch[:cap]
        return batch, expired

    def flush(self) -> int:
        """Dispatch up to max_requests pending requests in ONE device call;
        returns how many were served (0 = nothing pending). Requests whose
        deadline already passed are purged here — resolved with
        `DeadlineExceeded`, never rendered — before the batch is cut."""
        with self._cv:
            batch, expired = self._take_batch(self._now())
            self._inflight -= len(expired)
        if expired:
            self.expired += len(expired)
            telemetry.counter("serve.batcher.expired").inc(len(expired))
            for r in expired:
                r.fut.set_exception(DeadlineExceeded(
                    f"request for {str(r.image_id)[:12]} expired after "
                    f"{(self._now() - r.t_enq) * 1e3:.1f} ms in queue"))
                tracing.finish(r.trace, ok=False)
        if not batch:
            return 0
        cause = "full" if len(batch) >= self.max_requests else "deadline"
        bucket = pow2_bucket(len(batch))
        with telemetry.span("serve.batcher.flush", n=len(batch),
                            bucket=bucket, cause=cause,
                            seq=self.flushes) as flush_span:
            now = time.perf_counter()
            for r in batch:
                # enqueue -> dispatch: started on the submitting thread,
                # released by this flush (its parent); a traced request
                # sees it as its "queue" span
                telemetry.spans.record(
                    "serve.batcher.queue_wait", int(r.t_enq * 1e9),
                    int(now * 1e9), parent=flush_span.span_id,
                    riders=(r.trace,), rider_name="queue",
                    flush_cause=cause, batch_size=len(batch))
            telemetry.histogram(
                "serve.batcher.coalesce_size",
                edges=telemetry.pow2_buckets(1024)).record(len(batch))
            try:
                results = self.engine.render_many(
                    [(r.image_id, r.pose) for r in batch],
                    traces=[r.trace for r in batch],
                    images=[r.image for r in batch],
                    degraded=[r.degraded for r in batch])
                self.flushes += 1
                # resolving the futures runs their callbacks here
                with telemetry.span("serve.batcher.deliver", n=len(batch)):
                    done = time.perf_counter()
                    for r, res in zip(batch, results):
                        r.fut.set_result(res)
                        if self.slo is not None:
                            self.slo.record((done - r.t_enq) * 1e3,
                                            bucket=bucket, tier=r.tier)
                        tracing.finish(r.trace)
            except Exception as e:
                for r in batch:
                    if not r.fut.done():
                        r.fut.set_exception(e)
                    tracing.finish(r.trace, ok=False)
            finally:
                with self._cv:
                    self._inflight -= len(batch)
        return len(batch)

    def _wait_for_work(self) -> None:
        """Block (callers hold self._cv) until a request is pending or the
        batcher closes: the thread's `serve.batcher.idle` time."""
        if self._pending or self._closed:
            return
        with telemetry.span("serve.batcher.idle"):
            while not self._pending and not self._closed:
                self._cv.wait()

    def _linger(self, timeout: float) -> None:
        """Wait (callers hold self._cv) for co-riders with requests
        pending: the thread's `serve.batcher.linger` time."""
        with telemetry.span("serve.batcher.linger",
                            pending=len(self._pending)):
            self._cv.wait(timeout=timeout)

    def _run(self) -> None:
        while True:
            with self._cv:
                self._wait_for_work()
                if self._closed and not self._pending:
                    return
                # first request in: linger up to max_wait_s for co-riders
                # unless a full batch is already there (max_wait_ms=0
                # flushes immediately)
                if (self.max_wait_s > 0 and not self._closed
                        and len(self._pending) < self.max_requests):
                    self._linger(self.max_wait_s)
            self.flush()

    def close(self, timeout: float = 10.0) -> bool:
        """Drain pending requests and stop + JOIN the flush thread; returns
        True once the thread is confirmed dead. The join is bounded: a
        thread wedged in a device call can't hang the caller's exit — but a
        failed join is LOUD (a warning), never silent, because a dangling
        daemon thread racing interpreter teardown is exactly the flaky-exit
        bug this method exists to prevent."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=timeout)
        # drain on the caller's thread whatever the flush thread left
        # behind (it exits as soon as it sees _closed with an empty queue)
        while self.flush():
            pass
        if thread is not None and thread.is_alive():
            thread.join(timeout=timeout)
        if thread is not None and thread.is_alive():
            _log.warning(
                "batcher flush thread failed to join within %.1fs; "
                "it remains daemon and will die with the process", timeout)
            return False
        self._thread = None
        return True


class ContinuousBatcher(MicroBatcher):
    """Continuous-batching scheduler: keep the engine's pow2 pose buckets
    filled across in-flight requesters.

    Where MicroBatcher lingers ONCE per wakeup and then flushes whatever
    is pending, this scheduler runs a deadline loop: a batch dispatches the
    moment it is FULL (`max_requests`, one complete pow2 bucket), or when
    the OLDEST pending request's deadline (enqueue + `serve.max_wait_ms`)
    expires — no request waits past its deadline for co-riders, and a
    burst never waits at all. Admission is continuous: `submit` only takes
    the queue lock, which the flush path drops before the device call, so
    new requests keep boarding while a render is in flight and the next
    bucket is typically full by the time the engine returns.

    Same queue-wait / coalesce-size histograms and flush spans as
    MicroBatcher (the flush path is inherited): which trigger fired is the
    `serve.batcher.flush` span's `cause`, "full" or "deadline". Tests drive
    `_ready` and `flush()` directly with start=False (no timing
    dependence); `close()` joins the deadline loop like the base class.
    """

    def _ready(self, now: float) -> bool:
        """Dispatch decision (callers hold self._cv): full bucket, expired
        oldest deadline, or an immediate-mode (max_wait_ms=0) queue."""
        if len(self._pending) >= self.max_requests:
            return True
        if not self._pending:
            return False
        return (self.max_wait_s <= 0
                or now >= self._pending[0][3] + self.max_wait_s)

    def _run(self) -> None:
        while True:
            with self._cv:
                self._wait_for_work()
                if self._closed and not self._pending:
                    return
                now = time.perf_counter()
                if not self._closed and not self._ready(now):
                    # sleep only to the oldest deadline; a submit that
                    # fills the bucket notifies earlier. Loop back to
                    # re-decide instead of flushing blindly on wake.
                    self._linger(max(
                        0.0, self._pending[0][3] + self.max_wait_s - now))
                    continue
            self.flush()
