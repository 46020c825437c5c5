"""From a profiler trace to numbers: device busy time as the union of the
intervals in which an operation ran, the idle share, per-operation self time,
and each idle gap named by the benchmark span that covered it.

Started from a copy of tools/trace_summary.py (per-lane self-time tops from
the Chrome trace); this reads the `.xplane.pb` itself through
`jax.profiler.ProfileData`, needs nothing but JAX, and adds the busy union,
the idle share and the gap attribution. The yardstick lives here so that no
PR that claims a gain can change how a number is computed.

The neutral form (`load_xplane`'s output, and what the recorded trace under
`benchmark/tests/data/` holds) is
  {"planes": [{"name": str, "lines": [{"name": str,
      "events": [[name, start_ns, dur_ns, {stat: value}], ...]}]}]}
with device planes whole and host planes cut to the benchmark's own spans
(`bench.*`), all on the profiler's one clock.

What a v5e trace looks like (one read by hand, PR 26; PERF.md section 3):
one plane per chip named `/device:TPU:<n>` with the lines `Steps`,
`XLA Modules` (one event per program execution, `jit__train_step_impl(<id>)`),
`XLA Ops` (one event per executed HLO instruction, ~9,300 a train step; a
`while` or a `conditional` encloses the events of its body, hence self time)
and `Async XLA Ops` (copy-start/-done pairs, which overlap the ops and are
not counted as busy). The other planes are `#Chip0 Host Interface`,
`#Chip0 Misc`, `/device:CUSTOM:Megascale Trace`, `/host:metadata`,
`Task Environment`, and `/host:CPU`, whose lines are host threads: `python`
holds the TraceAnnotations, `main`, `pjrt-tpu-tasks/*` the runtime.
An `XLA Ops` event is NAMED by its whole HLO instruction and carries no
other metadata than its device offset and duration: no `tf_op`, no named
scope path. So the `encoder` / `decoder` scopes of the program do not show,
and a Pallas call is known by `custom_call_target="tpu_custom_call"` and by
the name its jitted wrapper gave the instruction (`KERNEL_KINDS` below).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.trace_window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# stats worth keeping on a device event (the rest are ids and offsets)
KEPT_STATS = ("hlo_op", "hlo_module", "hlo_category", "tf_op", "name",
              "long_name", "program_id", "run_id", "kernel_details",
              "source", "flops", "bytes_accessed", "model_flops")


def find_xplane(trace_dir: str):
    hits = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    return max(hits, key=os.path.getmtime) if hits else None


def load_xplane(path: str):
    """An `.xplane.pb` in the neutral form."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                stats = {}
                if device:
                    for key, value in ev.stats:
                        if key in KEPT_STATS and isinstance(
                                value, (str, int, float)):
                            stats[key] = (value if not isinstance(value, str)
                                          else value[:400])
                events.append([ev.name, float(ev.start_ns),
                               float(ev.duration_ns), stats])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ---------------- interval arithmetic ----------------

def union(intervals):
    """Sorted, disjoint union of [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def complement(disjoint, lo, hi):
    """The gaps of a sorted disjoint union inside [lo, hi)."""
    gaps, at = [], lo
    for s, e in disjoint:
        if s > at:
            gaps.append([at, s])
        at = max(at, e)
    if hi > at:
        gaps.append([at, hi])
    return gaps


def subtract(a, b):
    """Union of `a` minus union of `b`."""
    out = []
    b = union(b)
    for s, e in union(a):
        at = s
        for bs, be in b:
            if be <= at:
                continue
            if bs >= e:
                break
            if bs > at:
                out.append([at, bs])
            at = max(at, be)
            if at >= e:
                break
        if at < e:
            out.append([at, e])
    return out


def self_times(events):
    """Per event, its duration minus what its direct children cover (an
    enclosing `while` would else count its body twice). `events` are
    (start, end, ...) tuples; returns self seconds in the same order."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    child = [0.0] * len(events)
    stack = []
    for i in order:
        s, e = events[i][0], events[i][1]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
        stack.append(i)
    return [max(events[i][1] - events[i][0] - child[i], 0.0)
            for i in range(len(events))]


# ---------------- the reduction ----------------

def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def reduce(trace):
    """The neutral form reduced to what metrics read. Times in seconds,
    instants in nanoseconds on the profiler's clock. None where the trace
    holds no device plane with operations on it."""
    host_spans, window = [], None
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur, _ in line["events"]:
                if name == WINDOW_SPAN:
                    window = [start, start + dur]
                elif name.startswith(SPAN_PREFIX):
                    host_spans.append((start, start + dur,
                                       name[len(SPAN_PREFIX):]))
    host_spans.sort()
    device_planes = [p for p in trace["planes"]
                     if DEVICE_PLANE.match(p["name"]) and _line(p, OPS_LINE)]
    if not device_planes:
        return None
    if window is None:  # no window span recorded: the extent of the ops
        starts = [e[1] for p in device_planes for e in _line(p, OPS_LINE)]
        ends = [e[1] + e[2] for p in device_planes
                for e in _line(p, OPS_LINE)]
        window = [min(starts), max(ends)]
    w0, w1 = window
    devices = []
    for plane in sorted(device_planes, key=lambda p: p["name"]):
        ops = []
        for name, start, dur, stats in _line(plane, OPS_LINE):
            s, e = max(start, w0), min(start + dur, w1)
            if e > s:
                ops.append((s, e, name, stats))
        selfs = self_times(ops)
        busy = union([[s, e] for s, e, _, _ in ops])
        gaps = complement(busy, w0, w1)
        modules = [(start, start + dur, name)
                   for name, start, dur, _ in _line(plane, MODULES_LINE)
                   if start >= w0 and start + dur <= w1]
        devices.append({
            "name": plane["name"],
            "busy_s": total(busy) / 1e9,
            "ops": [{"name": n, "start_ns": s, "end_ns": e,
                     "self_s": selfs[i] / 1e9, "stats": st}
                    for i, (s, e, n, st) in enumerate(ops)],
            "modules": modules,
            "busy": busy,
            "gaps": gaps,
        })
    n_dev = len(devices)
    by_name = {}
    for dev in devices:
        for op in dev["ops"]:
            key = label(op["name"])
            by_name[key] = by_name.get(key, 0.0) + op["self_s"]
    gap_by_span = {}
    for dev in devices:
        for g0, g1 in dev["gaps"]:
            name = covering_span(host_spans, g0, g1)
            gap_by_span[name] = gap_by_span.get(name, 0.0) + (g1 - g0) / 1e9
    top = lambda d: [[k, v / n_dev] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    busy_s = sum(d["busy_s"] for d in devices) / n_dev
    window_s = (w1 - w0) / 1e9
    return {"window_ns": [w0, w1], "window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "devices": devices, "host_spans": host_spans,
            "device_ops": top(by_name), "idle_gaps": top(gap_by_span)}


def covering_span(host_spans, g0, g1) -> str:
    """The benchmark span that covers most of the gap [g0, g1); among equal
    covers the innermost (the one that started last)."""
    best, best_cover = "no_benchmark_span", 0.0
    for s, e, name in host_spans:
        if s >= g1:
            break
        cover = min(e, g1) - max(s, g0)
        if cover > 0 and cover >= best_cover:
            best, best_cover = name, cover
    return best


# ---------------- what metric readers ask ----------------

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


# On the v5e an event of `XLA Ops` is named by the whole HLO instruction:
#   %pallas_bilinear_sample.16 = f32[64,7,384,512]{...} custom-call(...),
#       custom_call_target="tpu_custom_call", ...
_INSTRUCTION = re.compile(
    r"^%?(?P<op>[^\s=]+) = (?P<type>\(?[a-z0-9]+\[[^\]]*\])?.*?"
    r"[\s)}](?P<opcode>[a-z][a-z0-9\-]*)\(")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
# Which kernel a Pallas call is, by the name the program's function gives
# the instruction (no `pallas_call` carries a `name=` yet: PERF.md, list
# for the tracing issue). v5e, PR 26: `pallas_bilinear_sample.N` and
# `_warp_bwd.N` are the warp, `fused_volume_render.N`, `_composite_bwd.N`
# and `fused_src_render_blend.N` the composite.
KERNEL_KINDS = (("warp", ("warp", "bilinear_sample")),
                ("composite", ("composite", "volume_render", "render_blend")))


def instruction(name: str):
    """(op name, opcode, result type) of an `XLA Ops` event name; the
    name itself and empty strings where it is no HLO instruction."""
    m = _INSTRUCTION.match(name)
    if not m:
        return name.lstrip("%").split(" ")[0], "", ""
    return m.group("op"), m.group("opcode"), (m.group("type") or "").lstrip(
        "(")


def label(name: str) -> str:
    """A short name for the breakdown: op, opcode and result type."""
    op, opcode, rtype = instruction(name)
    return " ".join(x for x in (op, opcode, rtype) if x)[:120]


def is_pallas_call(op) -> bool:
    """A Pallas kernel in the trace: a compiled `tpu_custom_call`."""
    if PALLAS_TARGET in op["name"]:
        return instruction(op["name"])[1] == "custom-call"
    return op["stats"].get("hlo_category") == "pallas"  # hand-built traces


def kernel_kind(op):
    """"warp", "composite" or None for a Pallas call."""
    if not is_pallas_call(op):
        return None
    name = instruction(op["name"])[0].lower()
    for kind, needles in KERNEL_KINDS:
        if any(n in name for n in needles):
            return kind
    return None


def is_kernel(kind: str):
    return lambda op: kernel_kind(op) == kind


def is_collective(op) -> bool:
    op_name, opcode, _ = instruction(op["name"])
    text = (opcode or op_name).lower()
    return any(text.startswith(c) for c in COLLECTIVES)


def op_seconds(reduced, match) -> float:
    """Self seconds of the operations `match(op)` accepts, averaged over the
    devices of the trace."""
    devs = reduced["devices"]
    return sum(op["self_s"] for d in devs for op in d["ops"]
               if match(op)) / len(devs)


def exposed_seconds(reduced, match, lo=None, hi=None) -> float:
    """Seconds (inside [lo, hi) where given) in which an operation `match`
    accepts ran on a device and no other operation did, averaged over the
    devices. An operation that encloses a matching one (a `while` around a
    collective) is its parent, not its competitor."""
    devs = reduced["devices"]
    out = 0.0
    for d in devs:
        mine = union([op["start_ns"], op["end_ns"]] for op in d["ops"]
                     if match(op) and op["self_s"] > 0)
        starts = [s for s, _ in mine]
        others = [[op["start_ns"], op["end_ns"]] for op in d["ops"]
                  if not match(op) and op["self_s"] > 0
                  and not _encloses_any(op, mine, starts)]
        alone = subtract(mine, others)
        if lo is not None:
            alone = clip(alone, lo, hi)
        out += total(alone) / 1e9
    return out / len(devs)


def _encloses_any(op, disjoint, starts) -> bool:
    """Does `op` enclose one of the sorted disjoint intervals? The first
    that starts inside it is the one that ends soonest."""
    i = bisect.bisect_left(starts, op["start_ns"])
    return i < len(disjoint) and disjoint[i][1] <= op["end_ns"]


def per_run(reduced, needle: str, match=None):
    """Over the executions of a program whose name holds `needle` that lie
    whole inside the traced window: (median seconds per execution,
    executions on one device). With `match`, the self time of the operations
    it accepts; without, the time in which any operation ran (the busy union
    inside the execution). The median, not the mean: the execution in
    progress when the profiler starts is recorded cut short (v5e, PR 26).
    Averaged over the devices."""
    per_device, runs = [], 0
    for d in reduced["devices"]:
        spans = [(s, e) for s, e, name in d["modules"] if needle in name]
        if not spans:
            continue
        runs = max(runs, len(spans))
        if match is None:
            each = [total(clip(d["busy"], s, e)) / 1e9 for s, e in spans]
        else:
            each = [0.0] * len(spans)
            starts = [s for s, _ in spans]
            for op in d["ops"]:
                i = bisect.bisect_right(starts, op["start_ns"]) - 1
                if i >= 0 and op["end_ns"] <= spans[i][1] and match(op):
                    each[i] += op["self_s"]
        per_device.append(statistics.median(each))
    if not per_device:
        return None, 0
    return sum(per_device) / len(per_device), runs


def module_runs(reduced, needle: str):
    """How many executions of a program whose name holds `needle` lie whole
    inside the traced window, on the first device."""
    return sum(1 for _, _, name in reduced["devices"][0]["modules"]
               if needle in name)
