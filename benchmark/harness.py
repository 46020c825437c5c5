"""What every cell shares: finding a cell's files by name, the device check,
seeds, the compile watch, the benchmark's own spans and the profiler window.

Nothing here knows a configuration, a traffic mix or a metric by name: those
live in `configs/`, `traffic/`, `drivers/` and `layer_metrics/`, and are
found through the names in BENCHMARK.json. A later PR adds files and entries
and edits none that is here.
"""

from __future__ import annotations

import contextlib
import hashlib
import importlib.util
import json
import logging
import os
import shutil
import tempfile
import threading
import time

from benchmark import trace_reduce

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# JAX's persistent compile cache, at a fixed path inside the checkout: the
# path is part of the cache key, and the two sides of a comparison must
# share nothing (run.py exports it before JAX is imported, so the program's
# own configure_compile_cache() takes it too)
COMPILE_CACHE_DIR = os.path.join(BENCH_DIR, ".cache", "jax")

# Test-only seam (benchmark/tests): the CPU rehearsal swaps these two, as
# chip_smoke.py's tests swap its PLATFORM. run.py has no option for it: a
# measurement that finds no chip fails.
REQUIRED_PLATFORM = "tpu"
PEAKS_FILE = os.path.join(BENCH_DIR, "peaks.json")


class BenchError(Exception):
    """The run cannot produce a result (exit code 1, no result line)."""


def say(msg: str) -> None:
    print("[bench +%7.2fs] %s" % (time.time() - process_start_time(), msg),
          flush=True)


# ---------------- time and seeds ----------------

_PROC_START = None


def process_start_time() -> float:
    """Unix time at which this process was started (from /proc; the time
    this module was imported where /proc cannot say)."""
    global _PROC_START
    if _PROC_START is None:
        try:
            with open("/proc/self/stat") as f:
                ticks = float(f.read().rsplit(")", 1)[1].split()[19])
            with open("/proc/uptime") as f:
                uptime = float(f.read().split()[0])
            age = uptime - ticks / os.sysconf("SC_CLK_TCK")
            _PROC_START = time.time() - max(age, 0.0)
        except (OSError, ValueError, IndexError):
            _PROC_START = time.time()
    return _PROC_START


def mix_seed(seed: int, *tags) -> int:
    """A 31-bit seed for one use, from the run's --seed (any whole number,
    larger than 32 bits hold) and the name of the use."""
    text = ":".join([str(int(seed))] + [str(t) for t in tags])
    return int.from_bytes(hashlib.sha256(text.encode()).digest()[:4],
                          "big") & 0x7FFFFFFF


# ---------------- files found by name ----------------

def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not os.path.exists(path):
        raise BenchError("no such benchmark file: %s" % path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of BENCHMARK.json's `workloads`, with its files."""

    def __init__(self, name: str, root: str = ""):
        self.root = root = root or ROOT
        self.bench_dir = os.path.join(root, "benchmark")
        self.manifest = load_json(os.path.join(root, "BENCHMARK.json"))
        entries = [w for w in self.manifest["workloads"] if w["name"] == name]
        if len(entries) != 1:
            raise BenchError("BENCHMARK.json has no cell named %r (it has %s)"
                             % (name, [w["name"]
                                       for w in self.manifest["workloads"]]))
        self.entry = entries[0]
        self.name = name
        self.chips = int(self.entry["chips"])
        # the traffic mix: a data file found by the `traffic` name
        self.workload = load_json(os.path.join(
            self.bench_dir, "traffic", self.entry["traffic"] + ".json"))
        cfg_entry = [c for c in self.manifest["configs"]
                     if c["name"] == self.entry["config"]]
        if len(cfg_entry) != 1:
            raise BenchError("cell %s names configuration %r, which "
                             "BENCHMARK.json does not list"
                             % (name, self.entry["config"]))
        self.config_file = load_json(os.path.join(root, cfg_entry[0]["file"]))
        self.driver_name = self.workload["driver"]

    def metric_entries(self, group: str):
        """The `end_to_end` or `per_layer` metrics this cell reports."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def driver(self):
        return load_module(os.path.join(self.bench_dir, "drivers",
                                        self.driver_name + ".py"),
                           "driver_" + self.driver_name)

    def layer_reader(self, metric_name: str):
        return load_module(os.path.join(self.bench_dir, "layer_metrics",
                                        metric_name + ".py"),
                           "layer_" + metric_name)

    def program_config(self):
        """The repo YAML this configuration loads, with its overrides and
        the traffic mix's own (`config_overrides` in the traffic file)."""
        from mine_tpu.config import load_config
        overrides = dict(self.config_file.get("overrides", {}))
        overrides.update(self.workload.get("config_overrides", {}))
        import mine_tpu
        program_root = os.path.dirname(os.path.dirname(
            os.path.abspath(mine_tpu.__file__)))
        config = load_config(
            os.path.join(program_root, self.config_file["yaml"]),
            extra_config=overrides)
        # the configuration's file states the sizes it is run at; a file
        # that says one thing while the program runs another is refused
        for key, want in self.config_file.get("as_run", {}).items():
            if key not in overrides and config.get(key) != want:
                raise BenchError(
                    "configuration %s states %s = %r, the program would "
                    "run %r" % (self.entry["config"], key, want,
                                config.get(key)))
        return config


# ---------------- the device ----------------

def load_peaks():
    return load_json(PEAKS_FILE)


def require_devices(chips: int):
    """The devices this cell runs on, and the peaks of their kind. Fails
    where JAX finds another platform than the chip's, fewer chips than the
    cell asks for, or a kind whose peaks nobody has written down."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != REQUIRED_PLATFORM:
        raise BenchError("JAX runs on %r here, not on %r: the benchmark "
                         "measures the chip and never falls back"
                         % (platform, REQUIRED_PLATFORM))
    if len(devices) < chips:
        raise BenchError("the cell asks for %d chip(s), JAX finds %d"
                         % (chips, len(devices)))
    kind = devices[0].device_kind
    peaks = load_peaks()
    if kind not in peaks:
        raise BenchError("no published peaks for device kind %r in %s "
                         "(known: %s)" % (kind, PEAKS_FILE, sorted(peaks)))
    return devices, dict(peaks[kind], kind=kind)


def device_block(devices, extra_peak_bytes: int = 0):
    """The result line's `device`. The allocator's peak on this backend does
    not count a running program's scratch (PERF.md, PR 24), so a driver hands
    in the largest temp size of the programs it timed, from the compiler's
    own memory analysis, and the peak is what is resident plus that."""
    resident, alloc_peak = 0, 0
    for d in devices:
        stats = d.memory_stats() or {}
        resident = max(resident, int(stats.get("bytes_in_use", 0)))
        alloc_peak = max(alloc_peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(alloc_peak,
                                     resident + int(extra_peak_bytes)),
            "memory_resident_bytes": resident,
            "memory_allocator_peak_bytes": alloc_peak,
            "memory_program_temp_bytes": int(extra_peak_bytes)}


def open_cell(name: str):
    """What run.py and sweep.py both do first: pin the compile cache to a
    fixed directory inside this checkout (whatever the environment says; the
    program's own configure_compile_cache() then follows), find the cell,
    start JAX with every program cacheable, watch its compiler, and check
    the devices. -> (cell, watch, devices, peaks)"""
    os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE_DIR
    cell = Cell(name)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    watch = CompileWatch().install()
    devices, peaks = require_devices(cell.chips)
    return cell, watch, devices, peaks


# ---------------- compiles ----------------

class CompileWatch(logging.Handler):
    """Every request JAX makes of its compiler, with the program's name and
    whether the persistent cache had it (jax._src.compiler logs both at
    DEBUG). A request inside the measured window is a fault, hit or miss:
    the shape was not warmed."""

    _HIT = "Persistent compilation cache hit for"
    _MISS = "PERSISTENT COMPILATION CACHE MISS for"

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.events = []  # (time.time(), "hit"|"miss", module name)
        self._lock = threading.Lock()

    def install(self):
        logger = logging.getLogger("jax._src.compiler")
        logger.addHandler(self)
        if logger.getEffectiveLevel() > logging.DEBUG:
            # the two lines are logged at DEBUG: open the logger for them,
            # and keep everything under a warning from travelling on to
            # JAX's own handler, which would print it
            logger.setLevel(logging.DEBUG)
            logger.propagate = False
            loud = logging.StreamHandler()
            loud.setLevel(logging.WARNING)
            logger.addHandler(loud)
        return self

    def emit(self, record):
        msg = record.msg if isinstance(record.msg, str) else ""
        kind = ("hit" if msg.startswith(self._HIT)
                else "miss" if msg.startswith(self._MISS) else None)
        if kind is None:
            return
        name = str(record.args[0]) if record.args else "?"
        with self._lock:
            self.events.append((time.time(), kind, name))

    def between(self, t0: float, t1: float):
        with self._lock:
            return [e for e in self.events if t0 <= e[0] <= t1]

    def summary(self, needle: str = ""):
        with self._lock:
            evs = [e for e in self.events if needle in e[2]]
        return {"hits": sum(e[1] == "hit" for e in evs),
                "misses": sum(e[1] == "miss" for e in evs),
                "compiled": sorted({e[2] for e in evs if e[1] == "miss"})}


# ---------------- the benchmark's own spans ----------------

class Spans:
    """Host-clock spans around the benchmark's own calls into the program.
    Each is also a `jax.profiler.TraceAnnotation`, so that in a traced run
    it lies on the profiler's clock beside the device's operations and an
    idle gap can be named by what this thread was doing."""

    def __init__(self):
        self.totals = {}  # name -> [count, seconds]
        self.recording = False
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        if self.recording:
            dt = time.perf_counter() - t0
            with self._lock:
                rec = self.totals.setdefault(name, [0, 0.0])
                rec[0] += 1
                rec[1] += dt

    def snapshot(self):
        with self._lock:
            return {k: {"count": v[0], "seconds": v[1]}
                    for k, v in self.totals.items()}


# ---------------- the profiler window ----------------


class TraceWindow:
    """A profiler trace over part of the measured window, in a run of its
    own (`--trace 1`). Starting and stopping the profiler each block their
    caller for a second or more, so a thread of its own does both and the
    driver's thread (the train loop, the load generator) is not held up:
    `start_after()` when the window opens, `join()` when it has closed,
    `reduced()` afterwards."""

    def __init__(self, seconds: float, keep_dir: str = ""):
        self.seconds = float(seconds)
        self.keep_dir = keep_dir
        self.dir = keep_dir or tempfile.mkdtemp(prefix="bench_trace_")
        self.stopped = False
        self.span = None   # (start, end) on time.perf_counter()'s clock
        self._thread = None

    def start_after(self, delay_s: float):
        self._thread = threading.Thread(target=self._run, args=(delay_s,),
                                        name="bench-trace", daemon=True)
        self._thread.start()

    def _run(self, delay_s):
        import jax
        time.sleep(max(0.0, delay_s))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # no per-call Python events
        options.host_tracer_level = 2     # TraceAnnotations
        jax.profiler.start_trace(self.dir, profiler_options=options)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            time.sleep(self.seconds)
        self.span = (t0, time.perf_counter())
        jax.profiler.stop_trace()
        self.stopped = True

    def join(self, timeout: float = 120.0):
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise BenchError("the profiler did not stop")

    def reduced(self):
        try:
            if not self.stopped:
                return None
            path = trace_reduce.find_xplane(self.dir)
            if path is None:
                return None
            return trace_reduce.reduce(trace_reduce.load_xplane(path))
        finally:
            if not self.keep_dir:
                shutil.rmtree(self.dir, ignore_errors=True)


# ---------------- statistics ----------------

def registry_window_mean(registry, name: str):
    """Mean of one of the program's registry histograms over the window:
    the difference of its sums over the difference of its counts between
    the snapshots at the window's two ends. None where nothing was
    recorded."""
    a = registry.get("start", {}).get(name) or {}
    b = registry.get("end", {}).get(name) or {}
    count = b.get("count", 0) - a.get("count", 0)
    if count <= 0:
        return None
    return (b.get("sum", 0.0) - a.get("sum", 0.0)) / count


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; `inf` entries (failed requests) sort last."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    if xs[hi] == float("inf"):
        return xs[hi] if pos > lo else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
