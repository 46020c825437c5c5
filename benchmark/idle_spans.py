"""Every idle nanosecond of the traced window given one owner: the program's
own span on the thread that feeds the device.

`trace_reduce.reduce` gives each device's idle gaps on the profiler's clock;
the program's span ring (`mine_tpu/telemetry/spans.py`) holds its spans on
`time.perf_counter_ns`. Two steps put the one on the other.

**The clock join** (`join`). The benchmark's own spans (`bench.*`, kept in
the reduced trace's `host_spans`) strictly contain program spans of the
ring, in order:
  `bench.step.dispatch` holds one `train.step.dispatch` whole
    (`train/trainer.py` `train_step`, called inside it);
  `bench.serve.submit` holds the start of one `serve.batcher.queue_wait`
    (its `t0` is the request's enqueue, taken inside `submit`).
A pair (b, r) allows the offsets d with b.start <= r.t0 + d and
r.t1 + d <= b.end. The benchmark spans that touch the window's edges are
dropped (the profiler cuts them); the rest are paired in order with the ring's
records at each alignment k, and the intervals of a pairing intersected.
Exactly one alignment may leave a non-empty interval, of at least
`MIN_PAIRS` pairs. A second containment (`REFINE`: the loop's take of a
staged batch inside `bench.feed.next`) then narrows it: each such record
between the first and the last of those benchmark spans inside the window
must fit exactly one of them at the offsets still allowed, and intersects
that span's interval (one nearer an edge may lie in a span the profiler
cut, and is left out where it fits none). The result may be at
most `MAX_WIDTH_NS` wide; the offset is its midpoint. Anything else is no
join: the readers report nothing and the log says why. No medians, no
nearest neighbours.

**The partition** (`split`). On the ring's clock, the window is cut at every
instant a span opens or closes; between two cuts each thread's open spans
are fixed. The dispatching thread is the one whose records hold the kind's
dispatch span (`train.step.dispatch`, `serve.batcher.flush`). Each piece of
a device's idle gap takes the share its kind's rule gives the open spans
there (`TRAIN`, `SERVE`), so every idle nanosecond has exactly one share;
shares are averaged over the devices and divided by the window, and so sum
to the trace's idle share. One table is logged a split: idle ms by share and
by the innermost span that names it.

Records that are not spans of their thread's stack are left out of the
partition: a request trace's copies (`trace` set) and intervals filed with
`spans.record` that lie outside their parent (a request's queue wait starts
on the submitting thread).

Every function returns None, and never raises, where the trace, the ring or
the spans are not there: a program without `data.stage.host_wait` reports
no host-batch share, and one without `data.stage.take` joins no train
cell's clocks.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, NamedTuple, Optional, Tuple

from benchmark import harness

MIN_PAIRS = 5
# The width is the least front margin of a dispatch (4-5 us) plus the least
# back margin of a take (TPU v5e host: 65-68 us feeding one chip, 126 us
# feeding four, where the stager and assembler threads hold the interpreter
# lock longer): the four-chip train cell's split waits for a join by the
# `span_id` that each `mine.*` annotation carries.
MAX_WIDTH_NS = 100_000
NO_SPAN = "(no span)"


class Anchor(NamedTuple):
    bench: str    # the benchmark span, without "bench."
    ring: str     # the program span it contains
    point: bool   # only the record's start lies inside the benchmark span


ANCHORS = (Anchor("step.dispatch", "train.step.dispatch", False),
           Anchor("serve.submit", "serve.batcher.queue_wait", True))
# Containments paired by the offsets the anchor leaves, not by order.
# `bench.step.dispatch` ends 0.8-2 ms after the program's dispatch (the
# train loop drops the old train state there; a TPU v5e host), so it bounds
# the offset from below only; the loop's take of a staged batch ends a few
# us before its `bench.feed.next` does, and bounds it from above.
REFINE = (Anchor("feed.next", "data.stage.take", False),)


class Kind(NamedTuple):
    dispatch: str     # the span whose thread feeds the device
    shares: Tuple[str, ...]
    # (names open on the dispatching thread, outermost first;
    #  {name: thread} of the spans open on the other threads) -> share
    rule: Callable
    # share -> the span that must be in the ring for the share to be read
    requires: Dict[str, str]
    # share -> the span, open on another thread, whose thread's innermost
    # span names the share's time in the table (default: the dispatching
    # thread's innermost)
    named_by: Dict[str, str]


def _train_rule(stack, others):
    if "data.stage.starved" in stack:
        if "data.stage.h2d" in others:
            return "h2d"
        if "data.stage.host_wait" in others:
            return "host_batch"
        return "unnamed"
    return "launch" if "train.step.dispatch" in stack else "unnamed"


def _serve_rule(stack, others):
    if "serve.batcher.idle" in stack or "serve.batcher.linger" in stack:
        return "sched"
    if stack and stack[-1] in ("serve.render.dispatch",
                               "serve.render.device_wait"):
        return "launch"
    return "host" if "serve.batcher.flush" in stack else "unnamed"


TRAIN = Kind("train.step.dispatch", ("h2d", "host_batch", "launch", "unnamed"),
             _train_rule,
             {"h2d": "data.stage.h2d", "host_batch": "data.stage.host_wait",
              "launch": "train.step.dispatch"},
             {"h2d": "data.stage.h2d", "host_batch": "data.stage.host_wait"})
SERVE = Kind("serve.batcher.flush", ("sched", "host", "launch", "unnamed"),
             _serve_rule,
             {"sched": "serve.batcher.idle", "host": "serve.batcher.flush",
              "launch": "serve.render.dispatch"},
             {})
KINDS = {"train": TRAIN, "serve": SERVE}


class Join(NamedTuple):
    offset_ns: float   # profiler instant = ring instant + offset
    width_ns: float    # the feasible interval's width; the error is half
    pairs: int         # anchor pairs
    anchor: str
    refined: int = 0   # pairs of `REFINE`


# ---------------- the clock join ----------------

def _inside(trace, bench):
    """The benchmark spans `bench` that lie strictly inside the window."""
    w0, w1 = trace["window_ns"]
    return sorted((s, e) for s, e, name in trace["host_spans"]
                  if name == bench and s > w0 and e < w1)


def join(trace, records) -> Optional[Join]:
    """The offset from the ring's clock to the profiler's, by the first
    anchor whose benchmark span lies inside the traced window, pinned
    further by `REFINE`."""
    for anchor in ANCHORS:
        outer = _inside(trace, anchor.bench)
        if not outer:
            continue
        mine = [r for r in records
                if r.name == anchor.ring and r.trace is None]
        interval = _align(outer, sorted(
            (r.t0_ns, r.t0_ns if anchor.point else r.t1_ns) for r in mine),
            anchor)
        if interval is None:
            return None
        threads = {r.thread for r in mine}
        refined = _refine(trace, [r for r in records if r.thread in threads],
                          *interval)
        if refined is None:
            return None
        lo, hi, more = refined
        if hi - lo > MAX_WIDTH_NS:
            harness.say("idle spans: the offset is known to %.1f us only "
                        "(%d bench.%s pairs, %d more), wider than %.0f" % (
                            (hi - lo) / 1e3, len(outer), anchor.bench, more,
                            MAX_WIDTH_NS / 1e3))
            return None
        return Join((lo + hi) / 2.0, hi - lo, len(outer), anchor.bench,
                    more)
    harness.say("idle spans: no anchor span (%s) inside the traced window"
                % ", ".join("bench." + a.bench for a in ANCHORS))
    return None


def _align(outer, inner, anchor):
    """(lo, hi) of the offsets that the one alignment of `inner` against
    `outer` allows, or None."""
    m = len(outer)
    if m < MIN_PAIRS:
        harness.say("idle spans: %d bench.%s inside the window, fewer than %d"
                    % (m, anchor.bench, MIN_PAIRS))
        return None
    found = []
    for k in range(len(inner) - m + 1):
        lo, hi = float("-inf"), float("inf")
        for (b0, b1), (r0, r1) in zip(outer, inner[k:k + m]):
            lo, hi = max(lo, b0 - r0), min(hi, b1 - r1)
            if lo > hi:
                break
        else:
            found.append((lo, hi))
    if len(found) != 1:
        harness.say("idle spans: %d alignments of %d bench.%s against %d %s "
                    "leave an offset, not one" % (len(found), m, anchor.bench,
                                                  len(inner), anchor.ring))
        return None
    return found[0]


def _refine(trace, records, lo, hi):
    """(lo, hi, pairs) after the containments of `REFINE`: each record of
    the anchor's thread that fits exactly one such benchmark span inside
    the window, at one of the offsets still allowed, narrows the interval
    by it. One that fits none is left out only where, at one of those
    offsets, it lies before the first of those spans or after the last: it
    may belong to a span the profiler cut at the window's edge (at an
    epoch's change the train loop takes a second batch inside one
    `bench.feed.next`, which may have begun before the window). Any other
    record refuses the join: None."""
    w0, w1 = trace["window_ns"]
    pairs = 0
    for anchor in REFINE:
        outer = _inside(trace, anchor.bench)
        first, last = (outer[0][0], max(b1 for _, b1 in outer)) if outer \
            else (w0, w1)
        for r in sorted((r for r in records
                         if r.name == anchor.ring and r.trace is None),
                        key=lambda r: r.t0_ns):
            fits = [(b0, b1) for b0, b1 in outer
                    if max(lo, b0 - r.t0_ns) <= min(hi, b1 - r.t1_ns)]
            if not fits and (r.t0_ns + lo <= first or r.t1_ns + hi >= last):
                continue
            if len(fits) != 1:
                harness.say("idle spans: a %s fits %d bench.%s, not one"
                            % (anchor.ring, len(fits), anchor.bench))
                return None
            (b0, b1), = fits
            lo, hi = max(lo, b0 - r.t0_ns), min(hi, b1 - r.t1_ns)
            pairs += 1
    return lo, hi, pairs


# ---------------- the partition ----------------

def _stack_records(records):
    """The records that lie on their thread's stack of open spans."""
    by_id = {r.span_id: r for r in records}
    out = []
    for r in records:
        if r.trace is not None or r.t1_ns <= r.t0_ns:
            continue
        parent = by_id.get(r.parent)
        if parent is not None and parent.thread == r.thread and (
                r.t0_ns < parent.t0_ns or r.t1_ns > parent.t1_ns):
            continue
        out.append(r)
    return out


def _dispatch_thread(records, name, lo, hi):
    counts = {}
    for r in records:
        if r.name == name and r.t1_ns > lo and r.t0_ns < hi:
            counts[r.thread] = counts.get(r.thread, 0) + 1
    return max(counts, key=counts.get) if counts else None


def _segments(records, thread, lo, hi, kind):
    """[(start, end, share, name)] covering [lo, hi) on the ring's clock:
    cut at every instant a span opens or closes, each piece given the share
    the kind's rule gives the spans open there."""
    cuts = {}
    for r in records:
        if r.t1_ns > lo and r.t0_ns < hi:
            cuts.setdefault(max(r.t0_ns, lo), []).append((1, r))
            cuts.setdefault(min(r.t1_ns, hi), []).append((0, r))
    cuts.setdefault(lo, [])
    cuts.setdefault(hi, [])
    times = sorted(cuts)
    open_by_thread = {}
    out = []
    for t, t_next in zip(times, times[1:]):
        for opens, r in cuts[t]:
            spans = open_by_thread.setdefault(r.thread, [])
            if opens:
                spans.append(r)
            else:
                spans.remove(r)
        stacks = {th: sorted(spans, key=lambda r: (r.t0_ns, -r.t1_ns,
                                                   r.span_id))
                  for th, spans in open_by_thread.items() if spans}
        mine = [r.name for r in stacks.get(thread, ())]
        others = {}
        for th, stack in stacks.items():
            if th != thread:
                for r in stack:
                    others.setdefault(r.name, th)
        share = kind.rule(mine, others)
        via = kind.named_by.get(share)
        named = stacks.get(others[via]) if via in others else stacks.get(
            thread)
        out.append((t, t_next, share, named[-1].name if named else NO_SPAN))
    return out


def _overlap(gaps, segments):
    """{(share, name): ns} of the gaps' overlap with the segments (both
    sorted and disjoint)."""
    out, i = {}, 0
    for g0, g1 in gaps:
        while i < len(segments) and segments[i][1] <= g0:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < g1:
            s, e, share, name = segments[j]
            ns = min(e, g1) - max(s, g0)
            if ns > 0:
                out[(share, name)] = out.get((share, name), 0.0) + ns
            j += 1
    return out


def split(trace, records, kind: Kind, clock: Optional[Join] = None):
    """{"shares": {share: % of the window or None}, "table": {(share,
    name): idle ms, mean over devices}, "join": Join} or None."""
    if trace is None or not trace.get("devices") or not records:
        return None
    clock = clock or join(trace, records)
    if clock is None:
        return None
    w0, w1 = trace["window_ns"]
    lo, hi = w0 - clock.offset_ns, w1 - clock.offset_ns
    if records[0].t1_ns > lo:
        harness.say("idle spans: the ring's oldest record closed after the "
                    "window opened: it dropped what the window needs")
        return None
    stacked = _stack_records(records)
    thread = _dispatch_thread(stacked, kind.dispatch, lo, hi)
    if thread is None:
        harness.say("idle spans: no %s inside the window" % kind.dispatch)
        return None
    segments = _segments(stacked, thread, lo, hi, kind)
    table = {}
    for dev in trace["devices"]:
        gaps = [(g0 - clock.offset_ns, g1 - clock.offset_ns)
                for g0, g1 in dev["gaps"]]
        for key, ns in _overlap(gaps, segments).items():
            table[key] = table.get(key, 0.0) + ns / len(trace["devices"])
    names = {r.name for r in records}
    shares = {}
    for share in kind.shares:
        need = kind.requires.get(share)
        ns = sum(v for (s, _), v in table.items() if s == share)
        shares[share] = (100.0 * ns / (w1 - w0)
                         if need is None or need in names else None)
    return {"shares": shares, "join": clock, "thread": thread,
            "table": {k: v / 1e6 for k, v in table.items()}}


# ---------------- what the readers call ----------------

def _ring():
    try:
        from mine_tpu.telemetry import spans
        return spans.records()
    except (ImportError, AttributeError):
        return None


def _say_table(kind_name, result, window_s, n_dev, seconds):
    clock = result["join"]
    harness.say("idle spans (%s): offset by %d bench.%s pairs and %d of "
                "%s, known to %.2f us; dispatching thread %s; split in %.2fs"
                % (kind_name, clock.pairs, clock.anchor, clock.refined,
                   " / ".join("bench." + a.bench for a in REFINE),
                   clock.width_ns / 1e3, result["thread"], seconds))
    rows = sorted(result["table"].items(), key=lambda kv: (kv[0][0], -kv[1]))
    harness.say("idle spans (%s): idle ms of the traced %.3f s by share and "
                "innermost span, mean over %d device(s): %s" % (
                    kind_name, window_s, n_dev, "; ".join(
                        "%s %s %.3f" % (share, name, ms)
                        for (share, name), ms in rows)))
    harness.say("idle spans (%s): %% of the window: %s" % (
        kind_name, ", ".join("%s %s" % (k, "none" if v is None else
                                         "%.4f" % v)
                             for k, v in result["shares"].items())))


def share(obs, kind_name: str, name: str):
    """One share of the window's idle time, in % of the traced window; the
    split is made once a run (kept in `obs`) and logged once."""
    key = "idle_spans." + kind_name
    if key not in obs:
        obs[key] = None
        trace = obs.get("trace")
        records = _ring()
        if trace is not None and records:
            t0 = time.perf_counter()
            try:
                obs[key] = split(trace, records, KINDS[kind_name])
            except Exception as e:  # noqa: BLE001 - never fails the run
                harness.say("idle spans (%s): no split: %r" % (kind_name, e))
            if obs[key] is not None:
                _say_table(kind_name, obs[key], trace["window_s"],
                           len(trace["devices"]), time.perf_counter() - t0)
    result = obs[key]
    return None if result is None else result["shares"].get(name)
