"""Observability for the serving stack: tracing spans + typed metrics.

Three pieces, on numpy alone (and ``jax.profiler``'s
``TraceAnnotation``):

  * :mod:`repro.obs.trace` — ring-buffered, ``perf_counter_ns``-stamped
    span tracer (``Tracer`` / ``NullTracer``); the engine records
    per-request lifecycle spans and per-step phase spans through it,
    and the phase spans land in a running profiler's trace as well.
  * :mod:`repro.obs.metrics` — ``MetricsRegistry`` of counters, gauges
    and raw-sample histograms; ``ServeStats`` / ``SwapStats`` /
    ``PrefixStats`` are views over one engine-owned registry.
  * Exporters: :mod:`repro.obs.perfetto` (Chrome trace-event JSON for
    ui.perfetto.dev) and :mod:`repro.obs.prom` (Prometheus text
    exposition).
  * The live plane: :mod:`repro.obs.windows` (rolling-window views
    over a registry), :mod:`repro.obs.slo` (multi-window burn-rate
    monitor), :mod:`repro.obs.flight` (anomaly-triggered incident
    bundles) and :mod:`repro.obs.http` (the ``/metrics`` / ``/healthz``
    / ``/slo`` / ``/vars`` scrape endpoint).

Everything here is host-side.  Calling a recorder from inside a jit'd
function records a tracer-time constant, not a runtime value — jaxlint
rule JL006 flags that statically.
"""

from .flight import FlightRecorder, SpikeDetector
from .http import MetricsServer, attach
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .perfetto import (
    export_perfetto,
    validate_trace,
    validate_trace_file,
)
from .slo import CRITICAL, OK, WARN, BurnRateMonitor, SloConfig
from .trace import NULL_TRACER, NullTracer, Tracer
from .windows import Ewma, WindowedView

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Tracer",
    "NullTracer",
    "NULL_TRACER",
    "export_perfetto",
    "validate_trace",
    "validate_trace_file",
    "Ewma",
    "WindowedView",
    "SloConfig",
    "BurnRateMonitor",
    "OK",
    "WARN",
    "CRITICAL",
    "SpikeDetector",
    "FlightRecorder",
    "MetricsServer",
    "attach",
]
