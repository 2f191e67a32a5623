"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A traced run writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.  The
device planes (``/device:TPU:<k>``) carry one event per device operation
on their ``XLA Ops`` line; the host plane carries the harness's own
``jax.profiler.TraceAnnotation`` spans, all named ``bench/<what>``, one of
them ``bench/window`` around the measured window.

From these, inside the window:

* ``busy_s``: the union of the operation intervals of each device used,
  averaged over those devices;
* ``device_ops``: device seconds per operation name, largest first;
* ``idle_gaps``: the longest intervals in which the first device ran no
  operation, each named by the innermost harness annotation that covers
  its middle.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
PREFIX = "bench/"
WINDOW = PREFIX + "window"
TOP = 10


@dataclass
class Reduction:
    busy_s: float
    window_s: float
    devices: int
    ops: int
    device_ops: list = field(default_factory=list)
    idle_gaps: list = field(default_factory=list)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def host_spans(data) -> list[tuple[str, float, float]]:
    """``(name, start_ns, end_ns)`` of every ``bench/`` annotation."""
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    return spans


def _name_of(t: float, spans: list[tuple[str, float, float]]) -> str:
    best, start = "none", -float("inf")
    for name, a, b in spans:
        if a <= t <= b and a >= start and name != WINDOW:
            best, start = name, a
    return best


def reduce(data) -> Reduction:
    """Reduce a loaded ``jax.profiler.ProfileData`` (see module doc)."""
    spans = host_spans(data)
    windows = [s for s in spans if s[0] == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    _, w0, w1 = windows[0]
    per_device, totals, n_ops = [], {}, 0
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        iv = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a, b = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if b <= a:
                    continue
                iv.append((a, b))
                totals[ev.name] = totals.get(ev.name, 0.0) + (b - a)
                n_ops += 1
        if iv:
            per_device.append(_union(iv))
    if not per_device:
        raise ValueError("no device operation ran inside the window")
    busy = sum(sum(b - a for a, b in u) for u in per_device) / len(per_device)
    gaps, prev = [], w0
    for a, b in per_device[0] + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    return Reduction(
        busy_s=busy * 1e-9,
        window_s=(w1 - w0) * 1e-9,
        devices=len(per_device),
        ops=n_ops,
        device_ops=[[k, v * 1e-9] for k, v in
                    sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]],
        idle_gaps=[[_name_of(0.5 * (a + b), spans), (b - a) * 1e-9]
                   for a, b in gaps[:TOP]],
    )


def reduce_dir(log_dir: str) -> Reduction:
    import jax

    return reduce(jax.profiler.ProfileData.from_file(find_xplane(log_dir)))
