"""Runtime telemetry: host spans, metrics, and timeline closure.

Three pieces (DESIGN.md §3.11):

* :mod:`repro.telemetry.trace` — a :class:`Tracer` producing nested
  host-timed ``Span(name, t0, t1, attrs)`` records, each also entered
  as a ``jax.profiler.TraceAnnotation`` so it lands on the profiler's
  host plane, exported as Chrome-trace / Perfetto ``trace_event`` JSON
  plus a schema-versioned ``repro/trace/v1`` record.
* :mod:`repro.telemetry.metrics` — a process-local registry of
  counters / gauges / histograms (wire bytes by algorithm×codec,
  PlanCache hits/misses/interning) with a JSON snapshot and a text
  summary.
* :mod:`repro.telemetry.closure` — the measured-vs-predicted timeline
  closure: replays each distinct IR stage as its own jitted collective
  with host timers, fits a single calibration scalar, and gates the
  per-stage residuals in a declared band (``BENCH_telemetry.json``).

Telemetry is off by default; every hook guards on :func:`enabled` and
records host-side data only, so compiled programs are the same with it
on or off.  Inside the compiled train step the layers are named by
``jax.named_scope`` instead (always on, HLO metadata only), and the
device trace of a profiler run times them.

``closure`` imports :mod:`repro.core`; it is deliberately NOT imported
here so that low-level core modules (the aggregator) can import
:mod:`repro.telemetry` without a cycle.
"""
from . import metrics, trace
from .metrics import REGISTRY as METRICS
from .metrics import MetricsRegistry, record_executor_cache, \
    record_plan_cache
from .trace import (
    TRACE_SCHEMA,
    Span,
    TelemetryConfig,
    Tracer,
    configure,
    enabled,
    get_tracer,
)

__all__ = [
    "METRICS",
    "MetricsRegistry",
    "Span",
    "TRACE_SCHEMA",
    "TelemetryConfig",
    "Tracer",
    "configure",
    "enabled",
    "get_tracer",
    "metrics",
    "record_executor_cache",
    "record_plan_cache",
    "trace",
]
