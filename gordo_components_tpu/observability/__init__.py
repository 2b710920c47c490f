"""Unified observability: metrics registry, Prometheus exposition, and
end-to-end request tracing.

Three small modules every layer shares:

- :mod:`.registry` — process-wide labeled Counter/Gauge/Histogram
  primitives (``REGISTRY`` is the one instance telemetry records to).
- :mod:`.exposition` — Prometheus text-format v0.0.4 rendering +
  validation (``GET /metrics?format=prometheus``).
- :mod:`.tracing` — contextvar trace ids propagated via the
  ``X-Gordo-Trace-Id`` header and stamped onto every log record.
- :mod:`.spans` — per-request stage timelines (queue_wait / dispatch /
  device_execute / fetch / ...) with explicit span-context capture
  across the engine's collector threads and the client's asyncio
  fan-out; Chrome trace-event (Perfetto) export per trace.
- :mod:`.flightrec` — the always-on bounded flight recorder behind
  ``/debug/requests`` (``RECORDER`` is the process instance).
- :mod:`.stitch` — cross-process trace stitching: the worker stamps its
  timeline onto the response (negotiated, size-capped), the router
  merges it under its ``route`` span with clock alignment.
- :mod:`.aggregate` — scrape-of-scrapes: merge N worker expositions
  into one fleet exposition (counters summed, histogram buckets
  merged, gauges per-worker-labeled, exemplars preserved).
- :mod:`.slo` — declared latency/availability objectives evaluated by
  multi-window burn rate over the collected histograms
  (``gordo_slo_*`` series, ``/slo``).
- :mod:`.logsetup` — text/JSON logging configuration for the CLI.
"""

from .exposition import CONTENT_TYPE, parse_prometheus_text, render_prometheus
from .flightrec import RECORDER, FlightRecorder
from .logsetup import configure_logging
from .registry import REGISTRY, Counter, Gauge, Histogram, Registry, get_registry
from .spans import SpanContext, Timeline
from .tracing import (
    TRACE_HEADER,
    current_or_new,
    get_trace_id,
    install_log_record_factory,
    new_trace_id,
    trace,
)

__all__ = [
    "CONTENT_TYPE",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "RECORDER",
    "REGISTRY",
    "Registry",
    "SpanContext",
    "TRACE_HEADER",
    "Timeline",
    "configure_logging",
    "current_or_new",
    "get_registry",
    "get_trace_id",
    "install_log_record_factory",
    "new_trace_id",
    "parse_prometheus_text",
    "render_prometheus",
    "trace",
]
