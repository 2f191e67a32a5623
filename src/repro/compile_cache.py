"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the service and
cluster-run mains) call :func:`enable_compile_cache` once, before their
first compile; importing the library never touches the cache, so tests
see JAX's defaults.

The rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here overrides it.  Otherwise the cache lives at one
fixed path inside the checkout (:data:`REPO_CACHE_DIR`, git-ignored).  A
fixed path matters because the path is part of what JAX matches on: a
directory named after a pid, a temp dir or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The in-checkout cache directory used when the environment names none.
REPO_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; return the directory used."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
