"""The persistent compile-cache rule of the entry points."""

import os

import jax

from repro import compile_cache
from repro.compile_cache import REPO_CACHE_DIR, enable_compile_cache


def _restore(before):
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_cache_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        _restore(before)


def test_unset_env_uses_fixed_repo_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == REPO_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR
        assert enable_compile_cache() == REPO_CACHE_DIR   # stable, no pid/time
    finally:
        _restore(before)


def test_repo_cache_dir_is_in_checkout_and_ignored():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.dirname(REPO_CACHE_DIR) == root
    with open(os.path.join(root, ".gitignore")) as f:
        ignored = {line.strip().rstrip("/") for line in f}
    assert os.path.basename(REPO_CACHE_DIR) in ignored


def test_import_does_not_touch_the_cache():
    import importlib

    before = jax.config.jax_compilation_cache_dir
    importlib.reload(compile_cache)
    assert jax.config.jax_compilation_cache_dir == before
