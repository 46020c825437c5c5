"""Flight recorder: always-on ring buffers + triggered incident bundles.

A production fleet's failure narrative ("p99 breached, admission went to
shed, shard 2 died, then the budget recovered") is spread across the event
stream, the metrics registry, the trace ring and the SLO window — and by
the time a human looks, the moment is gone. The `FlightRecorder` keeps a
bounded, host-side black box of the recent past:

  * the last N events (a tee on `events.emit` — every emitter feeds it,
    sink configured or not),
  * rolling registry snapshots at the caller's cadence (the pre-incident
    baseline postmortems diff against),
  * recent frozen `st1` step lines (train plane),
  * recent completed traces (read from `tracing.recent` at dump time),
  * the config dict + hash and the mtpu-aot1 environment fingerprint.

On a TRIGGER it atomically writes a self-contained incident bundle
directory `incidents/<utc-ts>-<reason>/` (manifest, events tail,
metrics.prom + metrics.json, snapshots, traces, SLO window, registered
state providers, config, environment, step lines), debounced so a breach
storm yields ONE bundle, with keep-last-K retention. Triggers arrive
three ways: watched event kinds through the tee (`serve.slo_breach`,
`serve.shard_dead`, admission escalation to shed, session failed frames,
`train.guard_abort`), the explicit `trigger()` API (chaos soaks, the train
loop's preemption/data-burst hooks), and SIGUSR2. A dump can also arm a
profiler window over the next K steps (`take_profile_request`, consumed
by the train loop) — retroactive-ish profiling of the aftermath.

Overhead discipline: the tee does one deque append + a dict lookup under
its own lock; dumps run on a dedicated worker thread (auto triggers) or
the caller's thread (explicit sync triggers), never inside an emitter's
critical section. Everything is host-side — nothing here touches jax
arrays, so recorder-on vs recorder-off outputs are bitwise identical
(test-pinned). Failure policy matches the event sink: a dump that cannot
write warns once and the run continues.

Lock order (analysis/locks.py): the bundle writer holds `recorder.dump`
(rank 2, below the whole serve plane) across state-provider callbacks
that re-enter fleet/batcher locks; the ring lock (`recorder.ring`, 18)
sits above every lock held at emit time. See LOCK_RANKS for derivation.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import shutil
import signal
import tempfile
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from mine_tpu.analysis.locks import ordered_condition, ordered_lock
from mine_tpu.telemetry import events as _events
from mine_tpu.telemetry import registry as _registry
from mine_tpu.telemetry import spans as _spans
from mine_tpu.telemetry import tracing as _tracing

_log = logging.getLogger(__name__)

BUNDLE_SCHEMA = "mtpu-inc1"
SPANS_IN_BUNDLE = 4096  # newest records of the span ring a bundle keeps

# Files every complete bundle carries; tools/postmortem.py refuses a
# bundle missing any of them (append-only: new files may join the set).
BUNDLE_FILES = ("manifest.json", "events.jsonl", "metrics.prom",
                "metrics.json", "snapshots.jsonl", "traces.json",
                "slo.json", "state.json", "config.json", "environment.json",
                "steplines.txt", "spans.jsonl")

# Event kinds the tee auto-triggers on. A predicate (or None = always)
# decides from the payload; edge-triggered sources (SLO breach, admission
# transitions, shard death) already emit once per edge, so the predicate
# never needs its own hysteresis — debounce caps the bundle rate anyway.
TRIGGER_KINDS: Dict[str, Optional[Callable[[Dict], bool]]] = {
    "serve.slo_breach": None,
    "serve.shard_dead": None,
    "train.guard_abort": None,
    "serve.admission": lambda f: f.get("state") == "shed",
    "serve.session_frame": lambda f: f.get("ok") is False,
    # a host leaving the ring (preemption/SIGTERM) is always postmortem-
    # worthy: the bundle captures the drain, the re-covered key range and
    # whatever pressure preceded it
    "serve.host_drain": None,
    # a circuit OPENING means a host ate breaker_threshold consecutive
    # transport failures — a breaker-open storm (several hosts at once)
    # is the fleet-wide network incident; debounce coalesces the storm
    # into one bundle instead of one per edge
    "serve.breaker": lambda f: f.get("state") == "open",
}


def _sanitize(reason: str) -> str:
    out = "".join(c if c.isalnum() or c in "._-" else "_"
                  for c in str(reason))
    return out[:64] or "trigger"


def _config_hash(config: Optional[Dict]) -> Optional[str]:
    if not config:
        return None
    try:
        blob = json.dumps(config, sort_keys=True, default=str)
    except Exception:
        return None
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _environment() -> Dict:
    """mtpu-aot1 environment fingerprint (serve/aot.py). Imported lazily:
    the telemetry package stays jax-free at import time."""
    try:
        from mine_tpu.serve.aot import env_fingerprint
        return env_fingerprint()
    except Exception as e:  # no jax / no devices: record that instead
        return {"schema": "mtpu-aot1", "error": str(e)}


class FlightRecorder:
    """Bounded black-box capture + triggered bundle dumps. Construct, then
    install as the process recorder via module `configure()` (which wires
    the events tee); `close()` joins the worker thread."""

    def __init__(self, out_dir: str, *,
                 events_tail: int = 256,
                 steplines: int = 64,
                 snapshots: int = 16,
                 debounce_s: float = 60.0,
                 keep: int = 5,
                 arm_profile_steps: int = 0,
                 traces_limit: int = 32,
                 config: Optional[Dict] = None):
        self.out_dir = str(out_dir)
        self.debounce_s = float(debounce_s)
        self.keep = max(1, int(keep))
        self.arm_profile_steps = max(0, int(arm_profile_steps))
        self.traces_limit = int(traces_limit)
        self.config = dict(config) if config else None
        self.config_hash = _config_hash(self.config)
        # ring state: everything below the cv's lock (rank 18 — above any
        # lock an emitter holds while the tee fires)
        self._cv = ordered_condition("telemetry.recorder.ring")
        self._events: deque = deque(maxlen=max(1, int(events_tail)))
        self._steplines: deque = deque(maxlen=max(1, int(steplines)))
        self._snapshots: deque = deque(maxlen=max(1, int(snapshots)))
        self._pending: List[tuple] = []  # (reason, trigger_event) queue
        self._last_dump: Optional[float] = None  # monotonic; debounce
        self._profile_request = 0
        self._signal_pending = False  # set by the SIGUSR2 handler, lockless
        self._prev_sigusr2 = None  # (our_handler, displaced_handler)
        self._stop = False
        self.triggers = 0
        self.dumps = 0
        self.suppressed = 0
        self.dump_failures = 0
        # the bundle writer's lock: rank 2, BELOW the serve plane, because
        # a dump calls state providers that re-enter batcher/fleet locks
        self._dump_lock = ordered_lock("telemetry.recorder.dump")
        self._slo = None
        self._providers: List[tuple] = []  # (name, callable) -> state.json
        self._bundle_seq = 0
        self._thread = threading.Thread(
            target=self._worker, daemon=True, name="mine-tpu-flight-recorder")
        self._thread.start()

    # ---------------- feeds ----------------

    def observe(self, kind: str, fields: Dict) -> None:
        """The events tee: called from `events.emit` for EVERY event, under
        whatever locks the emitter holds. One append + a trigger-table
        lookup; never dumps inline."""
        event = {"schema": _events.SCHEMA, "ts": time.time(),
                 "kind": str(kind)}
        event.update(fields)
        pred = TRIGGER_KINDS.get(kind, False)
        fire = pred is None or (pred is not False and bool(pred(fields)))
        with self._cv:
            self._events.append(event)
            if fire and self._reserve_locked(force=False):
                self._pending.append((str(kind), event))
                self._cv.notify()

    def observe_event(self, event: Dict) -> None:
        """Preload one already-built mtpu-ev1 event dict (original ts kept)
        into the ring — the offline path chaos_soak uses to bundle a dead
        leg's stream. Never triggers."""
        with self._cv:
            self._events.append(dict(event))

    def observe_stepline(self, line: str) -> None:
        with self._cv:
            self._steplines.append(str(line).strip())

    def snapshot_metrics(self, scope: str = "") -> None:
        """Append one rolling registry snapshot (call at log cadence): the
        pre-incident baseline `tools/postmortem.py` diffs metric values
        against."""
        snap = {"ts": time.time(), "scope": scope,
                "metrics": _registry.REGISTRY.snapshot()}
        with self._cv:
            self._snapshots.append(snap)

    def set_slo(self, slo) -> None:
        """Wire an SLOTracker; its snapshot() becomes the bundle's
        slo.json."""
        self._slo = slo

    def add_state_provider(self, name: str, fn: Callable[[], Dict]) -> None:
        """Register a `() -> dict` captured into state.json at dump time
        (fleet stats, health, train ops state). Called with NO recorder
        ring lock held, so providers may take serve-plane locks."""
        self._providers.append((str(name), fn))

    # ---------------- triggers ----------------

    def _reserve_locked(self, force: bool) -> bool:
        """Debounce/rate-limit decision; caller holds the ring lock. The
        slot is reserved at REQUEST time, so a storm of triggers inside one
        debounce window collapses to the single bundle already reserved."""
        self.triggers += 1
        now = time.monotonic()
        if not force:
            if self._pending:
                self.suppressed += 1
                return False
            if (self._last_dump is not None
                    and now - self._last_dump < self.debounce_s):
                self.suppressed += 1
                return False
        self._last_dump = now
        return True

    def trigger(self, reason: str, *, force: bool = False,
                sync: bool = True, **context) -> Optional[str]:
        """Explicit trigger (API / soaks / train hooks). `sync=True` writes
        the bundle on the calling thread and returns its path (None when
        debounced); `sync=False` enqueues to the worker. `force` bypasses
        the debounce (operator-initiated captures always land)."""
        event = {"reason": str(reason)}
        event.update(context)
        with self._cv:
            if not self._reserve_locked(force):
                return None
            if not sync:
                self._pending.append((str(reason), event))
                self._cv.notify()
                return None
        return self._dump(str(reason), event)

    def install_sigusr2(self) -> bool:
        """Arm `kill -USR2 <pid>` -> bundle. Best-effort: signal handlers
        install only on the main thread (False when that fails). The
        handler just sets a flag — it must not take locks the interrupted
        frame might hold — and the worker services it within its poll."""
        def _handler(signum, frame):
            self._signal_pending = True
        try:
            old = signal.signal(signal.SIGUSR2, _handler)
            # remember the displaced handler so close() can restore it —
            # the signal table is process-global and would otherwise pin
            # this recorder (and every state-provider closure behind it)
            # for the life of the process
            self._prev_sigusr2 = (_handler, old)
            return True
        except (ValueError, OSError):  # non-main thread / no signals here
            return False

    def take_profile_request(self) -> int:
        """Consume a pending profiler-arming request: the number of steps
        to profile (0 = none). The train loop polls this each step and
        opens a ProfileWindow over [next, next+K-1]."""
        with self._cv:
            k, self._profile_request = self._profile_request, 0
            return k

    # ---------------- dump ----------------

    def _worker(self) -> None:
        while True:
            with self._cv:
                while not (self._pending or self._stop
                           or self._signal_pending):
                    self._cv.wait(timeout=0.5)
                job = self._pending.pop(0) if self._pending else None
                sig, self._signal_pending = self._signal_pending, False
                if sig and job is None:
                    # operator signal: force past the debounce
                    self._reserve_locked(force=True)
                    job = ("sigusr2", {"reason": "sigusr2"})
                if job is None and self._stop:
                    return
            if job is not None:
                self._dump(*job)

    def _dump(self, reason: str, trigger_event: Optional[Dict]) -> \
            Optional[str]:
        try:
            return self._dump_inner(reason, trigger_event)
        except Exception:
            with self._cv:
                self.dump_failures += 1
            _log.warning("flight recorder: bundle dump failed (%s) — "
                         "continuing", reason, exc_info=True)
            return None

    def _dump_inner(self, reason: str,
                    trigger_event: Optional[Dict]) -> str:
        with self._dump_lock:
            with self._cv:  # copy the rings; release before any callout
                events_tail = list(self._events)
                steplines = list(self._steplines)
                snapshots = list(self._snapshots)
                self._bundle_seq += 1
                seq = self._bundle_seq
            state: Dict[str, Dict] = {}
            for name, fn in self._providers:
                try:
                    state[name] = fn()
                except Exception as e:  # a dead provider can't kill a dump
                    state[name] = {"error": str(e)}
            slo = {}
            if self._slo is not None:
                try:
                    slo = self._slo.snapshot()
                except Exception as e:
                    slo = {"error": str(e)}
            traces = _tracing.recent(self.traces_limit)
            metrics = _registry.REGISTRY.snapshot()
            from mine_tpu.telemetry.export import render_prometheus
            prom = render_prometheus()
            ts = time.time()
            stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime(ts))
            name = f"{stamp}-{_sanitize(reason)}"
            manifest = {
                "schema": BUNDLE_SCHEMA, "reason": str(reason), "ts": ts,
                "bundle": name, "trigger": trigger_event,
                "config_hash": self.config_hash,
                "counts": {"events": len(events_tail),
                           "snapshots": len(snapshots),
                           "steplines": len(steplines),
                           "traces": len(traces)},
                "recorder": {"events_tail": self._events.maxlen,
                             "debounce_s": self.debounce_s,
                             "keep": self.keep, "seq": seq},
            }
            os.makedirs(self.out_dir, exist_ok=True)
            # stage in a tmp dir, then one atomic rename: readers (the
            # /incidents route, postmortem) never see a half-written bundle
            tmp = tempfile.mkdtemp(dir=self.out_dir, prefix=".tmp-")
            try:
                self._write_files(tmp, manifest, events_tail, steplines,
                                  snapshots, traces, slo, state, metrics,
                                  prom)
                final = os.path.join(self.out_dir, name)
                n = 2
                while os.path.exists(final):  # same-second re-trigger
                    final = os.path.join(self.out_dir, f"{name}-{n}")
                    n += 1
                os.replace(tmp, final)
            except Exception:
                shutil.rmtree(tmp, ignore_errors=True)
                raise
            self._prune()
            with self._cv:
                self.dumps += 1
                if self.arm_profile_steps:
                    self._profile_request = self.arm_profile_steps
        # outside the dump lock: the emit re-enters the tee (ring rank 18)
        # and obs.incident is not a watched kind, so no re-trigger loop
        _events.emit("obs.incident", reason=str(reason), bundle=final,
                     events=len(events_tail), config_hash=self.config_hash)
        _log.warning("flight recorder: incident bundle written: %s (%s)",
                     final, reason)
        return final

    def _write_files(self, d, manifest, events_tail, steplines, snapshots,
                     traces, slo, state, metrics, prom) -> None:
        def jdump(fname, obj):
            with open(os.path.join(d, fname), "w") as f:
                json.dump(obj, f, indent=2, sort_keys=True, default=str)
                f.write("\n")
        jdump("manifest.json", manifest)
        jdump("traces.json", {"traces": traces})
        jdump("slo.json", slo)
        jdump("state.json", state)
        jdump("metrics.json", metrics)
        jdump("config.json", {"config_hash": self.config_hash,
                              "config": self.config})
        jdump("environment.json", _environment())
        with open(os.path.join(d, "events.jsonl"), "w") as f:
            for e in events_tail:
                f.write(json.dumps(e, default=_events._jsonify) + "\n")
        with open(os.path.join(d, "snapshots.jsonl"), "w") as f:
            for s in snapshots:
                f.write(json.dumps(s, default=_events._jsonify) + "\n")
        with open(os.path.join(d, "metrics.prom"), "w") as f:
            f.write(prom)
        with open(os.path.join(d, "steplines.txt"), "w") as f:
            f.write("\n".join(steplines) + ("\n" if steplines else ""))
        # the span ring (telemetry/spans.py), newest last, as the `span`
        # events export() would write: what every thread was doing
        with open(os.path.join(d, "spans.jsonl"), "w") as f:
            for rec in _spans.records()[-SPANS_IN_BUNDLE:]:
                f.write(json.dumps(
                    {"schema": _events.SCHEMA, "ts": manifest["ts"],
                     "kind": "span", **_spans.as_event_fields(rec)},
                    default=_events._jsonify) + "\n")

    def _prune(self) -> None:
        """Keep-last-K retention over completed bundle dirs (lexicographic
        = chronological: names lead with the UTC stamp)."""
        try:
            names = sorted(n for n in os.listdir(self.out_dir)
                           if not n.startswith(".tmp-")
                           and os.path.isdir(os.path.join(self.out_dir, n)))
        except OSError:
            return
        for n in names[:max(0, len(names) - self.keep)]:
            shutil.rmtree(os.path.join(self.out_dir, n),
                          ignore_errors=True)

    # ---------------- introspection ----------------

    def list_incidents(self) -> Dict:
        """/incidents body: bundles newest-first with their manifests'
        headline fields, plus recorder counters."""
        bundles = []
        try:
            names = sorted((n for n in os.listdir(self.out_dir)
                            if not n.startswith(".tmp-")
                            and os.path.isdir(
                                os.path.join(self.out_dir, n))),
                           reverse=True)
        except OSError:
            names = []
        for n in names:
            entry = {"bundle": n,
                     "path": os.path.join(self.out_dir, n)}
            try:
                with open(os.path.join(self.out_dir, n,
                                       "manifest.json")) as f:
                    man = json.load(f)
                entry.update(reason=man.get("reason"), ts=man.get("ts"),
                             counts=man.get("counts"))
            except Exception as e:
                entry["error"] = str(e)
            bundles.append(entry)
        with self._cv:
            counters = {"triggers": self.triggers, "dumps": self.dumps,
                        "suppressed": self.suppressed,
                        "dump_failures": self.dump_failures}
        return {"dir": self.out_dir, "incidents": bundles,
                "recorder": counters}

    def close(self) -> None:
        if self._prev_sigusr2 is not None:
            ours, displaced = self._prev_sigusr2
            self._prev_sigusr2 = None
            try:
                # only restore if the table still points at OUR handler —
                # someone re-arming SIGUSR2 after us keeps their handler
                if signal.getsignal(signal.SIGUSR2) is ours:
                    signal.signal(signal.SIGUSR2, displaced)
            except (ValueError, OSError):  # non-main thread: leave it
                pass
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=10)


# ------------------------------------------------------------- module state

# swap-only under the state lock (rank 3); close() of a replaced recorder
# runs OUTSIDE it, so the lock never nests into the worker join
_state_lock = ordered_lock("telemetry.recorder.state")
_recorder: Optional[FlightRecorder] = None


def configure(out_dir: str, **kwargs) -> FlightRecorder:
    """Install a process-wide FlightRecorder dumping into `out_dir` and
    wire the events tee to it. Replaces (and closes) any existing one."""
    global _recorder
    new = FlightRecorder(out_dir, **kwargs)
    with _state_lock:
        old, _recorder = _recorder, new
    _events.set_tee(new.observe)
    if old is not None:
        old.close()
    return new


def current_recorder() -> Optional[FlightRecorder]:
    with _state_lock:
        return _recorder


def maybe_trigger(reason: str, **context) -> None:
    """Fire-and-forget trigger for instrumented call sites (train loop's
    preemption/data-burst hooks): no-op without a configured recorder,
    async so any caller lock context is safe."""
    rec = current_recorder()
    if rec is not None:
        rec.trigger(reason, sync=False, **context)


def record_stepline(line: str) -> None:
    rec = current_recorder()
    if rec is not None:
        rec.observe_stepline(line)


def release(rec: Optional[FlightRecorder]) -> None:
    """Owner teardown: reset the module state if `rec` is still the
    installed recorder, else just close it (a later configure() won)."""
    global _recorder
    if rec is None:
        return
    with _state_lock:
        if _recorder is rec:
            _recorder = None
            current = True
        else:
            current = False
    if current:
        _events.set_tee(None)
    rec.close()


def reset() -> None:
    """Tests only: drop the recorder and the events tee."""
    global _recorder
    with _state_lock:
        old, _recorder = _recorder, None
    _events.set_tee(None)
    if old is not None:
        old.close()
