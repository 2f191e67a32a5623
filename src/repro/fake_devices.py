"""Child processes on fake CPU devices (``--xla_force_host_platform_device_count``).

Multi-device probes in the benchmarks and tests run a snippet in a child
that sees ``n`` virtual CPU devices.  The child is pinned to the CPU
(``JAX_PLATFORMS=cpu``): on an accelerator host the parent may already
hold the chip, and a child that reached for it would fail or hang — or
get one real device and the wrong mesh.  What such a child measures is a
CPU count, never a device timing.
"""

from __future__ import annotations

import os
import subprocess
import sys

SRC = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Printed (CSV-comment form) by every bench whose rows come from a child.
CPU_ROWS_NOTE = ("# rows below come from a child on fake CPU devices: "
                 "CPU counts, not device timings")


def run_on_cpu_devices(code: str, n_devices: int,
                       timeout: float) -> subprocess.CompletedProcess:
    """Run a python snippet in a child on ``n_devices`` fake CPU devices."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=timeout)
