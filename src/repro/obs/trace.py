"""Trace spans → Chrome trace-event JSON and the profiler (DESIGN.md §13).

A :class:`Tracer` records **complete events** (``"ph": "X"`` in the
Chrome trace-event format): name, category, start timestamp, duration,
thread id, and free-form ``args``.  Load the exported JSON in
``chrome://tracing`` or https://ui.perfetto.dev and a service run
renders as the familiar flame view — spans on one thread nest by time
containment, so the dispatcher's ``bucket`` span visibly contains its
``pack`` / ``cache`` / ``execute`` / ``resolve`` children.

:func:`phase` is the library's span: ``cluster`` wraps each of its
phases in one (``cluster``, ``cluster/input``, ``cluster/engine``, …),
each observed into the histogram :data:`PHASE_SECONDS` of a registry.

Every context-manager span — :func:`phase` and :meth:`Tracer.span` —
also enters a ``jax.profiler.TraceAnnotation`` named ``repro/<name>``,
so under ``jax.profiler.start_trace`` it lands on the profiler's host
plane, on the same clock as the device operations.  With no profiler
running the annotation is a sub-microsecond no-op.  Spans recorded
after the fact (:meth:`Tracer.add_span`) carry only host timestamps.

Per-request **trace ids** stitch the cross-thread story together: the
caller-side ``submit`` span carries ``args.trace_id``; the dispatcher's
per-bucket spans carry ``args.trace_ids`` (every request packed into
that dispatch); the per-request ``resolve`` span carries ``trace_id``
again.  Following one id through the export is following one request
through the service.

Design constraints (the §10 zero-recompile argument):

* **host-side only** — spans wrap calls *into* compiled code, never code
  inside a traced function; the profiler annotation is a host event and
  is never entered while JAX traces a function.
* **bounded** — events land in a ``deque(maxlen=...)``; a long-lived
  service keeps the most recent window instead of leaking.
* **cheap when off** — a disabled tracer's ``span()`` returns a shared
  no-op context manager: no timestamp read, no allocation, no lock.
  The measured on/off delta on service throughput is gated ≤ 5 % in CI
  (``bench_service --smoke``; EXPERIMENTS §Obs).

Timestamps come from ``time.perf_counter()`` rebased to the tracer's
creation, exported in microseconds (the trace-event unit).
"""

from __future__ import annotations

import itertools
import json
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Iterable

from jax.profiler import TraceAnnotation

from repro.obs.registry import MetricsRegistry, get_registry

#: Prefix of every span's profiler annotation.
ANNOTATION_PREFIX = "repro/"
#: Histogram of :func:`phase` seconds, labeled ``phase=<name>``.
PHASE_SECONDS = "cluster_phase_seconds"


@dataclass(frozen=True)
class SpanEvent:
    """One completed span (a Chrome trace-event complete event)."""

    name: str
    cat: str
    ts_us: float                # start, microseconds since tracer epoch
    dur_us: float
    tid: int
    pid: int = 0
    args: dict = field(default_factory=dict)

    def to_trace_event(self) -> dict:
        return {
            "name": self.name,
            "cat": self.cat,
            "ph": "X",
            "ts": self.ts_us,
            "dur": self.dur_us,
            "pid": self.pid,
            "tid": self.tid,
            "args": self.args,
        }


class _NullSpan:
    """Shared no-op context manager — the disabled tracer's span."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """Profiler annotation ``repro/<name>`` around a host-clock timing.

    On exit, exception or not, ``done(t0, t1)`` gets the
    ``perf_counter`` endpoints, taken inside the annotation.
    """

    __slots__ = ("_ann", "_done", "_value", "_t0")

    def __init__(self, name: str, done: Callable[[float, float], None],
                 value=None) -> None:
        self._ann = TraceAnnotation(ANNOTATION_PREFIX + name)
        self._done = done
        self._value = value

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self._value

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._done(self._t0, t1)
        return False


class Tracer:
    """Span recorder.  One per service run (or one global, your call).

    ``enabled=False`` builds a tracer whose every operation is a cheap
    no-op — instrumented code does not need its own ``if`` guards, and
    ``new_trace_id()`` still hands out unique ids so the metrics-only
    path keeps request identity.
    """

    def __init__(self, *, enabled: bool = True, max_events: int = 262144,
                 pid: int = 0) -> None:
        self.enabled = enabled
        self.pid = pid
        self._epoch = time.perf_counter()
        # hot path appends raw (name, cat, t0, t1, tid, args) tuples;
        # SpanEvent objects materialize only at export — a frozen
        # dataclass construction per span would dominate the span cost
        self._events: deque[tuple] = deque(maxlen=max_events)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._thread_names: dict[int, str] = {}

    # -- ids / time -----------------------------------------------------------

    def new_trace_id(self) -> int:
        """Unique per-request id (atomic: itertools.count holds the GIL)."""
        return next(self._ids)

    def now_us(self) -> float:
        return (time.perf_counter() - self._epoch) * 1e6

    def name_thread(self, name: str) -> None:
        """Label the calling thread in the exported trace metadata."""
        if self.enabled:
            with self._lock:
                self._thread_names[threading.get_ident()] = name

    # -- recording ------------------------------------------------------------

    def span(self, name: str, cat: str = "service", **args):
        """Context manager timing one span, also on the profiler's clock
        (``repro/<name>``); yields ``args``, which the caller may add to
        before exit.  No-op when disabled."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(name, lambda t0, t1: self._record(name, cat, t0, t1,
                                                       args), args)

    def add_span(self, name: str, t0: float, t1: float, cat: str = "service",
                 **args) -> None:
        """Record a span from already-measured ``perf_counter`` endpoints
        (instrumentation that must not sit inside the timed region)."""
        if self.enabled:
            self._record(name, cat, t0, t1, args)

    def _record(self, name: str, cat: str, t0: float, t1: float,
                args: dict) -> None:
        # no lock: CPython deque.append is GIL-atomic, and readers only
        # ever take a point-in-time list() copy (also atomic) — the lock
        # guards the thread-name table, not the event window
        self._events.append((name, cat, t0, t1, threading.get_ident(), args))

    def _materialize(self, raw: tuple) -> SpanEvent:
        name, cat, t0, t1, tid, args = raw
        return SpanEvent(
            name=name,
            cat=cat,
            ts_us=(t0 - self._epoch) * 1e6,
            dur_us=max(t1 - t0, 0.0) * 1e6,
            tid=tid,
            pid=self.pid,
            args=args,
        )

    # -- export ---------------------------------------------------------------

    def events(self) -> list[SpanEvent]:
        """Point-in-time copy of the (bounded) event window."""
        raws = list(self._events)       # atomic snapshot under the GIL
        return [self._materialize(r) for r in raws]

    def export(self) -> dict:
        """Chrome trace-event JSON object (``json.dump`` it verbatim)."""
        raws = list(self._events)       # atomic snapshot under the GIL
        with self._lock:
            names = dict(self._thread_names)
        trace_events = [self._materialize(r).to_trace_event() for r in raws]
        for tid, name in names.items():
            trace_events.append({
                "name": "thread_name", "ph": "M", "pid": self.pid,
                "tid": tid, "args": {"name": name},
            })
        return {
            "traceEvents": trace_events,
            "displayTimeUnit": "ms",
            "otherData": {"exporter": "repro.obs.trace"},
        }

    def write(self, path: str) -> int:
        """Write the export to ``path``; returns the event count."""
        doc = self.export()
        with open(path, "w") as fh:
            json.dump(doc, fh)
        return sum(1 for e in doc["traceEvents"] if e.get("ph") == "X")

    def clear(self) -> None:
        with self._lock:
            self._events.clear()


#: Shared always-off tracer — the default for every instrumented
#: component, so the uninstrumented path pays one attribute check.
NULL_TRACER = Tracer(enabled=False, max_events=1)


def spans_by_name(events: Iterable[SpanEvent], name: str) -> list[SpanEvent]:
    """Test/analysis helper: all spans with a given name."""
    return [e for e in events if e.name == name]


def phase(name: str, registry: MetricsRegistry | None = None,
          tracer: Tracer | None = None) -> _Span:
    """Context manager timing one phase of a library call.

    Enters the profiler annotation ``repro/<name>``, observes the
    phase's seconds into ``registry``'s :data:`PHASE_SECONDS` histogram
    (``phase=<name>``; the process-global :func:`get_registry` by
    default) on exit, exception or not, and, given a ``tracer``, records
    the span there too.
    """
    hist = (get_registry() if registry is None else registry).histogram(
        PHASE_SECONDS, "Seconds per phase of a library call")

    def done(t0: float, t1: float) -> None:
        hist.observe(t1 - t0, phase=name)
        if tracer is not None:
            tracer.add_span(name, t0, t1, cat="cluster")

    return _Span(name, done)
