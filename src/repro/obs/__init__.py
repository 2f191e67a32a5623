"""repro.obs — dependency-free observability layer (DESIGN.md §13).

One metrics substrate + one span substrate for the whole repo:

* :mod:`~repro.obs.registry` — thread-safe :class:`MetricsRegistry`
  (labeled counters, gauges, bounded-window histograms with
  percentiles).  ``service.batcher.ServiceMetrics`` and
  ``service.cache.CacheStats`` sit on it; the distributed chain and
  fault runtime feed the process-global default (:func:`get_registry`).
* :mod:`~repro.obs.trace` — :class:`Tracer` span API (context manager +
  record-from-timestamps), per-request trace ids, Chrome trace-event
  JSON export (renders in ``chrome://tracing`` / Perfetto), and
  :func:`phase`, the library's span (``cluster`` and its phases).
  Context-manager spans also land on the JAX profiler's host plane as
  ``repro/<name>`` annotations.
* :mod:`~repro.obs.export` — Prometheus-style text exposition, JSON
  dump, and the periodic dumper the service load driver uses.

Everything is host-side by design: instrumentation wraps calls *into*
compiled code and never runs inside a traced function, so the §10
zero-recompile contract is untouched (the on/off throughput delta is
gated ≤ 5 % in CI).
"""

from repro.obs.export import (
    PeriodicDumper,
    dump_json,
    prometheus_text,
    registry_json,
)
from repro.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    reset_registry,
)
from repro.obs.trace import (
    NULL_TRACER,
    PHASE_SECONDS,
    SpanEvent,
    Tracer,
    phase,
    spans_by_name,
)

__all__ = [
    "NULL_TRACER",
    "PHASE_SECONDS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "PeriodicDumper",
    "SpanEvent",
    "Tracer",
    "dump_json",
    "get_registry",
    "phase",
    "prometheus_text",
    "registry_json",
    "reset_registry",
    "spans_by_name",
]
