"""Request-level tracing: one trace per serve request, spans across threads.

The registry answers "how slow is the p99"; this module answers "WHY was
that one request slow". Every request entering the serve path
(`ServeFleet.submit`, serve_cli's per-image loop, the SLO bench) can start
a trace; the stages it passes through — front-end routing, batcher queue
wait, a sync encode, bucket padding, the jitted render — each record a
child span, and every span lands in the mtpu-ev1 event stream as one

    {"kind": "trace.span", "trace": <id>, "span": <id>, "parent": <id|null>,
     "name": ..., "ms": ..., "t_off_ms": ..., ...fields}

line, so `tools/obs_report.py` (and anything else reading the stream) can
reassemble a request's full latency anatomy offline. The root span's event
is emitted LAST, at `finish()` — a stream containing a trace's root is a
stream containing the whole trace.

Design constraints, same as the rest of the package:
  * HOST-SIDE ONLY and stdlib-only. Starting a trace never touches a jax
    array; the bitwise-parity test in tests/test_serve_trace_e2e.py holds
    rendering identical with tracing on vs off.
  * Cross-THREAD by explicit handoff, not thread-locals: a request's
    TraceContext rides inside the batcher's pending tuple from the
    submitting thread to the flush thread, and the program spans that
    serve it (telemetry/spans.py: the queue wait, the pad, the render)
    forward themselves to it as riders. TraceContext is therefore
    thread-safe. Every child span is the same record the program's spans
    write, in the same ring, with `trace` set to this trace's id.
  * Sampling is decided ONCE at `start()` (head sampling): an unsampled
    request costs one RNG draw and nothing else — no context object, no
    span records, no events.

Completed traces additionally land in a small in-memory ring buffer
(`recent()`) so the ops endpoint's `/traces/recent` can show live anatomy
without re-reading the event file.
"""

from __future__ import annotations

import os
import random
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from mine_tpu.analysis.locks import ordered_lock
from mine_tpu.telemetry import events as _events
from mine_tpu.telemetry import registry as _registry
from mine_tpu.telemetry import spans as _spans

EVENT_KIND = "trace.span"
DEFAULT_RECENT = 256


def _new_id() -> str:
    """64-bit random hex id (os.urandom: unique across processes too, so
    multi-process streams funneled into one file never collide)."""
    return os.urandom(8).hex()


class TraceContext:
    """One in-flight request's trace: a root span plus child spans recorded
    from any thread. Obtain via `tracing.start(...)`; close via
    `tracing.finish(ctx)`. All methods are safe to call concurrently;
    spans recorded after finish are dropped (the trace is sealed)."""

    __slots__ = ("trace_id", "root_id", "name", "fields", "ts",
                 "_t0_ns", "_lock", "spans", "finished", "total_ms", "ok")

    def __init__(self, name: str, **fields):
        self.trace_id = _new_id()
        self.root_id = _new_id()
        self.name = str(name)
        self.fields = dict(fields)
        self.ts = time.time()           # wall clock, for the recent() view
        self._t0_ns = time.perf_counter_ns()  # origin for t_off_ms
        self._lock = ordered_lock("telemetry.tracing.ctx")
        self.spans: List[Dict] = []
        self.finished = False
        self.total_ms: Optional[float] = None
        self.ok = True

    def _off_ms(self, t_ns: int) -> float:
        return (t_ns - self._t0_ns) / 1e6

    def add_span(self, name: str, ms: float,
                 t0: Optional[float] = None,
                 parent: Optional[str] = None, **fields) -> Optional[Dict]:
        """Record one already-measured child span. `ms` is the duration;
        `t0` is the span's start as a time.perf_counter() reading (used for
        the trace-relative offset `t_off_ms`; defaults to now - ms).
        `parent` defaults to the root span. Returns the span record (None
        if the trace was already finished)."""
        dur_ns = int(round(float(ms) * 1e6))
        t0_ns = (time.perf_counter_ns() - dur_ns if t0 is None
                 else int(t0 * 1e9))
        return self.note(name, t0_ns, t0_ns + dur_ns, parent, fields)

    def note(self, name: str, t0_ns: int, t1_ns: int,
             parent: Optional[str], fields: Dict,
             span_id: Optional[int] = None,
             cause: Optional[int] = None) -> Optional[Dict]:
        """File one interval (`time.perf_counter_ns` instants) as a child
        span of this trace: the telemetry ring's record with `trace` set
        to this trace's id, and one `trace.span` event. `parent` is the hex
        id of a span of this trace (default: the root); `span_id` / `cause`
        are the ring's integer ids of the program span this is, and of the
        one that caused it (telemetry/spans.py calls this for a span opened
        with `trace=` or `riders=`)."""
        rec = {"trace": self.trace_id, "span": _new_id(),
               "parent": parent if parent is not None else self.root_id,
               "name": str(name), "ms": round((t1_ns - t0_ns) / 1e6, 3),
               # clamp: a span cannot start before its trace (the default
               # now-ms back-dating of a pre-measured duration may land
               # fractionally before the root's origin)
               "t_off_ms": round(max(0.0, self._off_ms(t0_ns)), 3)}
        rec.update(fields)
        with self._lock:
            if self.finished:
                return None
            self.spans.append(rec)
        _spans.append(str(name), t0_ns, t1_ns, cause, self.trace_id, fields,
                      span_id=span_id)
        _events.emit(EVENT_KIND, **rec)
        return rec

    def child(self, name: str, parent: Optional[str] = None, **fields):
        """Context manager measuring a block as a child span (a
        `telemetry.span` that files itself under this trace):

            with ctx.child("route", owner_shard=o):
                ...
        """
        return _spans.span(name, trace=self, trace_parent=parent, **fields)

    def annotate(self, **fields) -> None:
        """Attach fields to the ROOT span (carried on its finish event)."""
        with self._lock:
            self.fields.update(fields)


class _Tracer:
    """Process-wide tracer state: sampling rate + completed-trace ring."""

    def __init__(self):
        self._lock = ordered_lock("telemetry.tracing.tracer")
        self.sample = 0.0
        self._rng = random.Random()
        self._recent: deque = deque(maxlen=DEFAULT_RECENT)

    def configure(self, sample: Optional[float] = None,
                  recent_capacity: Optional[int] = None) -> None:
        with self._lock:
            if sample is not None:
                s = float(sample)
                if not 0.0 <= s <= 1.0:
                    raise ValueError(
                        f"trace sample rate must be in [0, 1], got {s}")
                self.sample = s
            if recent_capacity is not None:
                if recent_capacity < 1:
                    raise ValueError(
                        f"recent_capacity must be >= 1, "
                        f"got {recent_capacity}")
                self._recent = deque(self._recent,
                                     maxlen=int(recent_capacity))

    def start(self, name: str, sample: Optional[float] = None,
              **fields) -> Optional[TraceContext]:
        with self._lock:
            rate = self.sample if sample is None else float(sample)
            if rate <= 0.0:
                return None
            if rate < 1.0 and self._rng.random() >= rate:
                return None
        _registry.counter("serve.trace.sampled").inc()
        return TraceContext(name, **fields)

    def finish(self, ctx: Optional[TraceContext], ok: bool = True,
               **fields) -> None:
        if ctx is None:
            return
        now = time.perf_counter_ns()
        with ctx._lock:
            if ctx.finished:
                return
            ctx.finished = True
            ctx.ok = bool(ok)
            ctx.total_ms = round(ctx._off_ms(now), 3)
            ctx.fields.update(fields)
            root = {"trace": ctx.trace_id, "span": ctx.root_id,
                    "parent": None, "name": ctx.name, "ms": ctx.total_ms,
                    "t_off_ms": 0.0, "ok": ctx.ok}
            root.update(ctx.fields)
            spans = [root] + list(ctx.spans)
        _events.emit(EVENT_KIND, **root)
        _registry.histogram("serve.trace.e2e_ms").record(ctx.total_ms)
        _registry.counter("serve.trace.finished").inc()
        summary = {"trace": ctx.trace_id, "name": ctx.name, "ts": ctx.ts,
                   "ms": ctx.total_ms, "ok": ctx.ok, "spans": spans}
        with self._lock:
            self._recent.append(summary)

    def recent(self, n: Optional[int] = None) -> List[Dict]:
        """Most-recent completed traces, newest first (JSON-safe dicts:
        what /traces/recent serves)."""
        with self._lock:
            out = list(self._recent)
        out.reverse()
        return out if n is None else out[:max(0, int(n))]

    def reset(self) -> None:
        """Tests only: sampling off, ring cleared."""
        with self._lock:
            self.sample = 0.0
            self._recent = deque(maxlen=DEFAULT_RECENT)


_TRACER = _Tracer()


def configure(sample: Optional[float] = None,
              recent_capacity: Optional[int] = None) -> None:
    """Set the process-wide head-sampling rate (0 disables, 1 traces every
    request) and/or the completed-trace ring capacity."""
    _TRACER.configure(sample=sample, recent_capacity=recent_capacity)


def start(name: str, sample: Optional[float] = None,
          **fields) -> Optional[TraceContext]:
    """Begin a trace, or return None when the sampling decision says no —
    every downstream hook (`add_span`, `finish`) accepts/ignores None, so
    call sites never branch. `sample` overrides the configured rate for
    this one decision (the bench and tests pass 1.0)."""
    return _TRACER.start(name, sample=sample, **fields)


def finish(ctx: Optional[TraceContext], ok: bool = True, **fields) -> None:
    """Seal a trace: emits the root trace.span event (parent null), records
    serve.trace.e2e_ms, and files the trace into the recent() ring.
    Idempotent; no-op on None."""
    _TRACER.finish(ctx, ok=ok, **fields)


def recent(n: Optional[int] = None) -> List[Dict]:
    return _TRACER.recent(n)


def reset() -> None:
    _TRACER.reset()
