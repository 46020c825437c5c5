"""Unified telemetry layer: metrics registry + event stream + span timers.

Every subsystem that used to keep private observability state (the train
loop's hand-formatted step line, serve's cache-attribute stats, the
pipeline's error counters, one-time warnings standing in for counters) now
also reports through this package, so train, serve and chaos paths emit one
coherent, parseable surface:

  registry.py  process-wide counters / gauges / fixed-bucket histograms
               with p50/p90/p99 extraction (README "Observability" has the
               metric catalog)
  events.py    append-only schema-versioned JSONL event sink — non-fatal on
               write failure, validated in CI (tools/validate_events.py),
               consumed by tools/obs_report.py
  spans.py     THE span primitive: span(name, **fields) -> a bounded ring
               of records, the `<name>_ms` histogram, and a
               jax.profiler.TraceAnnotation "mine.<name>" (README
               "Observability" lists the spans)
  programs.py  the jitted programs' device ops named by layer: HLO
               instruction name -> encoder / decoder / render / ...
  tracing.py   request-level traces: per-request span trees carried across
               threads (the same record with `trace` set), emitted as
               trace.span events (serve path anatomy)
  slo.py       rolling-window SLO tracker: sliding p50/p99 vs a
               configurable objective, error-budget burn, breach events
  export.py    Prometheus text exposition of the registry + the opt-in
               HTTP ops endpoint (/metrics /healthz /slo /traces/recent)
  stepline.py  the frozen "time: schema=st1 ..." step-time line + its one
               shared parser
  profiler.py  opt-in jax.profiler trace windows over exact train-loop step
               ranges (telemetry.profile_steps = [start, stop])
  recorder.py  flight recorder: bounded ring buffers of the recent past
               (events/steplines/metric snapshots) that dump atomic
               incident bundles on triggers — rendered by
               tools/postmortem.py, listed at /incidents
  resource.py  opt-in process-vitals sampler thread (RSS, threads, fds,
               GC) publishing into the registry
  hostsync.py  host_readback(reason): declared device->host syncs — the
               transfer-guard sanitizer's allowlist (tools/audit.py)

Dependency-free (stdlib only) and strictly host-side: nothing in here is
ever traced, so instrumentation cannot change jitted numerics or add a
device sync — the bitwise-parity tests in tests/test_telemetry.py and
tests/test_serve_trace_e2e.py hold the package to that.
"""

from mine_tpu.telemetry import programs, recorder, resource, spans, tracing
from mine_tpu.telemetry.events import (KIND_FIELDS, emit, ensure_configured,
                                       validate_file, validate_line)
from mine_tpu.telemetry.export import (OpsServer, parse_prometheus,
                                       render_prometheus)
from mine_tpu.telemetry.hostsync import host_readback, readback_counts
from mine_tpu.telemetry.profiler import ProfileWindow
from mine_tpu.telemetry.recorder import FlightRecorder
from mine_tpu.telemetry.resource import ResourceSampler
from mine_tpu.telemetry.registry import (REGISTRY, Counter, Gauge, Histogram,
                                         MetricsRegistry, counter,
                                         default_latency_buckets_ms, gauge,
                                         histogram, pow2_buckets)
from mine_tpu.telemetry.slo import SLOTracker
from mine_tpu.telemetry.spans import SpanRecord, span
from mine_tpu.telemetry.stepline import (STEP_KEYS, STEP_SCHEMA, TIME_KEYS,
                                         format_step_line, parse_line,
                                         parse_lines)
from mine_tpu.telemetry.tracing import TraceContext

__all__ = [
    "FlightRecorder", "KIND_FIELDS", "OpsServer", "REGISTRY", "Counter",
    "Gauge", "Histogram", "MetricsRegistry", "ProfileWindow",
    "ResourceSampler", "SLOTracker", "SpanRecord", "TraceContext",
    "STEP_KEYS", "STEP_SCHEMA", "TIME_KEYS", "counter",
    "default_latency_buckets_ms", "emit", "ensure_configured",
    "format_step_line", "gauge", "histogram", "host_readback", "parse_line",
    "parse_lines", "parse_prometheus", "pow2_buckets", "programs",
    "readback_counts", "recorder", "render_prometheus", "resource", "span",
    "spans", "tracing",
    "validate_file", "validate_line",
]
