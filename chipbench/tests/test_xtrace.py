"""The reduction from a profiler trace to busy time, idle gaps and ops,
on a synthetic trace with a v5e trace's plane and line names."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from chipbench import xtrace

def _ev(name, a, b):
    return NS(name=name, start_ns=a, end_ns=b, duration_ns=b - a)


def _trace(device_events, host_events):
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=host_events)]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[_ev("jit_step", 0, 10_000)]),
            NS(name="XLA Ops", events=device_events)]),
        NS(name="/device:TPU:0 SparseCore 0", lines=[
            NS(name="XLA Ops", events=[_ev("sc", 0, 10_000)])]),
    ])


def test_busy_union_idle_gaps_and_top_ops():
    ops = [_ev("fusion.1", 1000, 2000), _ev("fusion.2", 1500, 2500),
           _ev("copy", 6000, 7000), _ev("outside", 9500, 12000)]
    host = [_ev("bench/window", 1000, 10_000), _ev("bench/cluster", 900, 2600),
            _ev("bench/prepare", 3000, 5500), _ev("not-ours", 2500, 6000)]
    r = xtrace.reduce(_trace(ops, host))
    # busy: [1000, 2500] + [6000, 7000] + [9500, 10000] inside the window
    assert r.busy_s == pytest.approx(3000e-9)
    assert r.window_s == pytest.approx(9000e-9) and r.devices == 1
    assert r.device_ops[0] == ["fusion.1", pytest.approx(1000e-9)]
    assert {k for k, _ in r.device_ops} == {"fusion.1", "fusion.2", "copy",
                                            "outside"}
    # gaps: [2500, 6000] under prepare, [7000, 9500] under no annotation
    assert r.idle_gaps == [["bench/prepare", pytest.approx(3500e-9)],
                           ["none", pytest.approx(2500e-9)]]


def test_no_window_or_no_device_work_is_an_error():
    with pytest.raises(ValueError, match="bench/window"):
        xtrace.reduce(_trace([], [_ev("bench/cluster", 0, 5)]))
    with pytest.raises(ValueError, match="no device operation"):
        xtrace.reduce(_trace([_ev("late", 20_000, 30_000)],
                             [_ev("bench/window", 0, 10_000)]))

