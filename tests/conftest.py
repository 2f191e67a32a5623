"""Shared test fixtures.  NOTE: no XLA_FLAGS here — unit tests must see the
real single-device CPU; multi-device tests spawn subprocesses pinned to the
CPU (``JAX_PLATFORMS=cpu``) with ``--xla_force_host_platform_device_count``
themselves (:mod:`repro.fake_devices`)."""

import signal
import threading

import numpy as np
import pytest

from repro.fake_devices import run_on_cpu_devices


def pytest_addoption(parser):
    parser.addini(
        "hang_timeout",
        "per-test wall-clock limit in seconds (SIGALRM-based, no plugin "
        "needed); 0 disables.  A hung service/batcher loop then FAILS that "
        "test instead of stalling the whole suite.",
        default="0",
    )


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    limit = float(item.config.getini("hang_timeout") or 0)
    if (
        limit <= 0
        or not hasattr(signal, "SIGALRM")
        or threading.current_thread() is not threading.main_thread()
    ):
        return (yield)

    def _alarm(signum, frame):
        pytest.fail(
            f"test exceeded hang_timeout={limit:.0f}s (pytest.ini) — "
            "probable hang in a service/dispatcher loop",
            pytrace=False,
        )

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 560) -> str:
    """Run a python snippet in a subprocess with n fake CPU devices."""
    out = run_on_cpu_devices(code, n_devices, timeout=timeout)
    assert out.returncode == 0, f"subprocess failed:\n{out.stdout}\n{out.stderr}"
    return out.stdout


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def random_distance_matrix(rng, n: int, dim: int = 4,
                           squared: bool = False) -> np.ndarray:
    X = rng.normal(size=(n, dim))
    D = ((X[:, None, :] - X[None, :, :]) ** 2).sum(-1)
    return D if squared else np.sqrt(D)
