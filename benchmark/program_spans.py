"""What the readers of the program's own spans share (PR 28).

The program (`mine_tpu/telemetry/spans.py`) records one span at every layer
boundary: a ring of records, and a registry histogram `<name>_ms` a span.
The serve driver hands over two snapshots of every `serve.*` registry name
(`obs["registry"]`), so the `.serve` readers take differences of those; the
train driver hands no registry over, so the `.train` readers import
`mine_tpu.telemetry` themselves, after the window, and read the ring. The
step's device operations are named by layer through
`mine_tpu.telemetry.programs` (HLO instruction name -> layer).

Every function returns None, and never raises, where the program has no such
span, ring or map: a program from before PR 28 reports none of these metrics.
"""

from __future__ import annotations

import re
import time

from benchmark import harness, trace_reduce

STEP_SPAN = "train.step.dispatch"
_OP_NAME = re.compile(r'op_name="([^"]*)"')


# ---------------- serve: differences of the registry's snapshots ----------

def window_sum_ms(registry, name: str):
    """Milliseconds recorded into one of the program's span histograms
    inside the window; None where neither snapshot knows the name."""
    a = registry.get("start", {}).get(name)
    b = registry.get("end", {}).get(name)
    if not isinstance(b, dict):
        return None
    a = a if isinstance(a, dict) else {}
    return b.get("sum", 0.0) - a.get("sum", 0.0)


def window_share(obs, names, minus=()):
    """Percent of the window that the spans `names` cover (less those of
    `minus`, their children); None where one of them was never recorded."""
    sums = [window_sum_ms(obs["registry"], n) for n in list(names) + list(
        minus)]
    if any(s is None for s in sums) or not obs.get("window_s"):
        return None
    total = sum(sums[:len(names)]) - sum(sums[len(names):])
    return 100.0 * total / (obs["window_s"] * 1e3)


def window_mean_ms(obs, names):
    """Sum of the window means of the histograms `names` (ms a call)."""
    means = [harness.registry_window_mean(obs["registry"], n) for n in names]
    return None if any(m is None for m in means) else sum(means)


# ---------------- train: the ring of span records ----------------

def _ring(name: str):
    try:
        from mine_tpu.telemetry import spans
        return spans.records(name)
    except (ImportError, AttributeError):
        return None


def step_interval(obs):
    """(start_ns, end_ns, steps) of the window on the ring's clock: from the
    end of the dispatch before the window's first to the end of its last
    (the driver makes no step after the window)."""
    steps = int(obs["counters"].get("steps") or 0)
    recs = _ring(STEP_SPAN)
    if not steps or not recs or len(recs) < steps:
        return None
    mine = recs[-steps:]
    start = recs[-steps - 1].t1_ns if len(recs) > steps else mine[0].t0_ns
    return start, mine[-1].t1_ns, steps


def ring_ms(obs, name: str):
    """(milliseconds, count) of the spans `name` that started inside the
    window's steps; None where the ring or the steps are not there."""
    interval = step_interval(obs)
    recs = _ring(name)
    if interval is None or recs is None:
        return None
    start, end, _ = interval
    inside = [r for r in recs if start <= r.t0_ns <= end]
    return sum((r.t1_ns - r.t0_ns) / 1e6 for r in inside), len(inside)


def ring_ms_per(obs, name: str, per: str):
    """Milliseconds of the spans `name` per step ("step") or per span of
    their own ("span"); None where there is nothing to divide by."""
    got = ring_ms(obs, name)
    if got is None:
        return None
    ms, count = got
    n = step_interval(obs)[2] if per == "step" else count
    return ms / n if n else None


def step_gap_max_ms(obs):
    """The longest time between the starts of two consecutive dispatches of
    the window's steps: the loop's sync at log cadence in a quiet run, a
    stall (of the device, of the host) where a run reads low."""
    steps = int(obs["counters"].get("steps") or 0)
    recs = _ring(STEP_SPAN)
    if steps < 2 or not recs or len(recs) < steps:
        return None
    mine = recs[-steps:]
    return max(b.t0_ns - a.t0_ns for a, b in zip(mine, mine[1:])) / 1e6


# ---------------- train: the step's device operations by layer ------------

_classifiers = {}


def _classifier(program: str):
    """op -> layer name or None, for the operations of one traced program;
    None where the program keeps no map of it (before PR 28)."""
    if program not in _classifiers:
        try:
            from mine_tpu.telemetry import programs
            known = programs.registered(program)
        except (ImportError, AttributeError):
            known = False
        _classifiers[program] = _make_classifier(
            programs, program) if known else None
    return _classifiers[program]


def _make_classifier(programs, program: str):
    by_name = {}          # event name -> layer
    by_instruction = []   # the program's map, fetched at the first need

    def classify(op):
        name = op["name"]
        if name not in by_name:
            m = _OP_NAME.search(name)
            if not by_name:   # one uncut event name, for whoever reads the log
                harness.say("a device event of %s, uncut (%s op_name): %s"
                            % (program, "holds its" if m else "no",
                               name[:600]))
            if m:   # the event carries its scope path itself
                by_name[name] = programs.layer_of(m.group(1))
            else:
                if not by_instruction:
                    t0 = time.perf_counter()
                    by_instruction.append(programs.layers(program) or {})
                    harness.say("programs.layers(%r): %d instructions named "
                                "by layer in %.1fs" % (
                                    program, len(by_instruction[0]),
                                    time.perf_counter() - t0))
                by_name[name] = by_instruction[0].get(
                    trace_reduce.instruction(name)[0])
        return by_name[name]

    return classify


def layer_ms(obs, layer):
    """Self time (ms a step) of the step program's operations whose layer is
    `layer` (None: those the map gives no layer)."""
    trace = obs["trace"]
    if trace is None:
        return None
    program = obs["counters"].get("step_program", "train_step")
    classify = _classifier(program)
    if classify is None:
        return None
    try:
        secs, runs = trace_reduce.per_run(
            trace, program, lambda op: classify(op) == layer)
    except Exception as e:  # noqa: BLE001 - a reader never fails the run
        harness.say("no per-layer split of %s: %r" % (program, e))
        _classifiers[program] = None
        return None
    return None if not runs else secs * 1e3
