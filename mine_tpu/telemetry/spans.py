"""The program's one span primitive: one record, three sinks.

    with telemetry.span("serve.batcher.flush", n=8, cause="full"):
        ...

Entering a span opens a `jax.profiler.TraceAnnotation("mine." + name,
span_id=<int>)`, so that under a profiler the span lies on the device's
clock beside the operations it caused; leaving it appends one `SpanRecord`
to a bounded in-memory ring and records the duration into the registry
histogram `<name>_ms`. With `emit=True` (low-frequency spans: checkpoints)
it is also written at once as one {"kind": "span", "name": ..., "ms": ...}
event; everything else reaches the event stream when `export()` writes the
ring out (CLI shutdown, the flight recorder's bundle).

The record is (name, t0_ns, t1_ns, thread, span_id, parent, trace, fields):
`time.perf_counter_ns` instants, the thread's name, a process-wide integer
id (the same number the TraceAnnotation carries as its `span_id` stat: ring
record and profiler event join exactly by it; the profiler's own times are
relative to its session and do not line up with any host clock), the id of
the span that caused it (the enclosing span on this thread unless one is
passed explicitly across a thread hand-off), the id of the request trace
it belongs to or None, and the caller's fields.

Names are absolute ("ckpt.restore", never composed from the enclosing
span's); nesting is recorded in `parent`. Exceptions propagate untouched;
the duration still records with ok=false so a failing save's cost is
visible, not lost.

`record(name, t0_ns, t1_ns, ...)` files an interval that was measured
elsewhere (a request's time in the queue starts on the submitting thread).
A span with `riders` (the `TraceContext`s of the requests it serves)
forwards itself to each of them under `rider_name`; `trace=<ctx>` makes the
span a child of that one request's trace (`TraceContext.child`).
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

from mine_tpu.telemetry import events as _events
from mine_tpu.telemetry import registry as _registry

RING_CAPACITY = 65536
ANNOTATION_PREFIX = "mine."


class SpanRecord(NamedTuple):
    name: str
    t0_ns: int
    t1_ns: int
    thread: str
    span_id: int
    parent: Optional[int]
    trace: Optional[str]
    fields: Dict

    @property
    def ms(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e6


_ring: "collections.deque[SpanRecord]" = collections.deque(
    maxlen=RING_CAPACITY)
_ring_lock = threading.Lock()  # a leaf: nothing is acquired under it
_ids = itertools.count(1)      # next() is atomic under the GIL
_tls = threading.local()


def _stack() -> List[int]:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def current() -> Optional[int]:
    """Id of the innermost open span on this thread, or None."""
    s = _stack()
    return s[-1] if s else None


_annotation_cls = None


def _annotation():
    """`jax.profiler.TraceAnnotation`, imported on first use: importing
    telemetry stays stdlib-only."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation
        _annotation_cls = TraceAnnotation
    return _annotation_cls


def append(name: str, t0_ns: int, t1_ns: int, parent: Optional[int],
           trace_id: Optional[str], fields: Dict,
           span_id: Optional[int] = None) -> int:
    """One record into the ring (and nowhere else); a new id unless the
    caller has one. `span` and `TraceContext.note` file through this."""
    if span_id is None:
        span_id = next(_ids)
    rec = SpanRecord(name, int(t0_ns), int(t1_ns),
                     threading.current_thread().name, span_id, parent,
                     trace_id, fields)
    with _ring_lock:
        _ring.append(rec)
    return span_id


class span:
    """Context manager; see the module docstring. After the block,
    `t0_ns`, `t1_ns`, `ms` and `span_id` hold what was recorded. Setting
    `histogram = False` inside the block keeps this one duration out of
    `<name>_ms` (a render call that turns out to be a bucket's first,
    compile-dominated visit); the ring record is written all the same."""

    __slots__ = ("name", "emit", "registry", "histogram", "parent", "trace",
                 "trace_parent", "riders", "rider_name", "fields", "span_id",
                 "t0_ns", "t1_ns", "_ann")

    def __init__(self, name: str, emit: bool = False,
                 registry: Optional[_registry.MetricsRegistry] = None,
                 parent: Optional[int] = None, trace=None,
                 trace_parent: Optional[str] = None, riders=(),
                 rider_name: Optional[str] = None, **fields):
        if not name:
            raise ValueError("span needs a non-empty name")
        self.name = str(name)
        self.emit = emit
        self.registry = registry
        self.histogram = True
        self.parent = parent
        self.trace = trace
        self.trace_parent = trace_parent
        self.riders = riders
        self.rider_name = rider_name
        self.fields = fields
        self.span_id: Optional[int] = None
        self.t0_ns = self.t1_ns = 0

    @property
    def ms(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e6

    def __enter__(self) -> "span":
        self.span_id = next(_ids)
        stack = _stack()
        if self.parent is None and stack:
            self.parent = stack[-1]
        stack.append(self.span_id)
        self._ann = _annotation()(ANNOTATION_PREFIX + self.name,
                                  span_id=self.span_id)
        self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1_ns = time.perf_counter_ns()
        self._ann.__exit__(exc_type, exc, tb)
        stack = _stack()
        # unwind to OUR frame even if an inner span leaked (an inner
        # __exit__ that never ran because its thread died): the stack must
        # not corrupt every later span on this thread
        while stack and stack[-1] != self.span_id:
            stack.pop()
        if stack:
            stack.pop()
        if exc_type is not None:
            self.fields.setdefault("ok", False)
        try:
            self._file()
        except Exception:
            pass  # telemetry never turns a timed block's success into a fail
        return False  # propagate exceptions

    def _file(self) -> None:
        """The finished interval into its sinks."""
        fields = self.fields
        if self.trace is not None:
            # a child of one request's trace: the context files the record
            # (ring + trace.span event) under its own id
            self.trace.note(self.name, self.t0_ns, self.t1_ns,
                            self.trace_parent, fields, span_id=self.span_id,
                            cause=self.parent)
        else:
            if self.emit:
                fields = dict(fields, live=True)  # export() skips it
            append(self.name, self.t0_ns, self.t1_ns, self.parent, None,
                   fields, span_id=self.span_id)
            if self.histogram:
                (self.registry or _registry.REGISTRY).histogram(
                    self.name + "_ms").record(self.ms)
            if self.emit:
                _events.emit("span", name=self.name, ms=round(self.ms, 3),
                             **{"ok": True, **fields})
        for rider in self.riders:
            if rider is not None:
                rider.note(self.rider_name or self.name, self.t0_ns,
                           self.t1_ns, None, fields, cause=self.span_id)


def record(name: str, t0_ns: int, t1_ns: int, parent: Optional[int] = None,
           trace=None, riders=(), rider_name: Optional[str] = None,
           emit: bool = False, **fields) -> int:
    """File an interval measured elsewhere (no TraceAnnotation: it is
    over). `parent` defaults to the innermost open span on this thread.
    Returns the record's span id."""
    sp = span(name, emit=emit, parent=parent if parent is not None
              else current(), trace=trace, riders=riders,
              rider_name=rider_name, **fields)
    sp.span_id, sp.t0_ns, sp.t1_ns = next(_ids), int(t0_ns), int(t1_ns)
    sp._file()
    return sp.span_id


def records(name: Optional[str] = None) -> List[SpanRecord]:
    """A copy of the ring (oldest first), optionally of one name only."""
    with _ring_lock:
        out = list(_ring)
    if name is not None:
        out = [r for r in out if r.name == name]
    return out


def as_event_fields(rec: SpanRecord) -> Dict:
    """A record as the fields of one `span` event."""
    out = {"ok": True, **rec.fields}
    out.update(name=rec.name, ms=round(rec.ms, 3), t0_ns=rec.t0_ns,
               t1_ns=rec.t1_ns, thread=rec.thread, span_id=rec.span_id,
               parent=rec.parent)
    return out


_exported_upto = 0   # span id: export() is incremental


def export() -> int:
    """Write the ring into the event stream as `span` events (CLI
    shutdown): every record that did not reach the stream when it closed
    (`emit=True` spans and request-trace spans already did) and that no
    earlier call wrote. Returns how many were written."""
    global _exported_upto
    recs = [r for r in records() if r.span_id > _exported_upto]
    if recs:
        _exported_upto = max(r.span_id for r in recs)
    recs = [r for r in recs if r.trace is None and not r.fields.get("live")]
    for r in recs:
        _events.emit("span", **as_event_fields(r))
    return len(recs)


def reset() -> None:
    """Tests only: empty the ring (ids keep counting)."""
    with _ring_lock:
        _ring.clear()
