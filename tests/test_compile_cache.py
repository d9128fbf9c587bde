"""The entry points' compilation-cache helper (runtime/compile_cache.py).

The helper honours ``JAX_COMPILATION_CACHE_DIR`` and otherwise picks one
fixed directory under the repository root.  These tests never turn the
cache on: they check the choice, and call the setter only where the
environment already names a directory, which leaves JAX alone.
"""
from pathlib import Path

import jax

from repro.runtime import compile_cache


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.compile_cache_dir() is None
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_repo_dir_without_env(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.compile_cache_dir() == root / ".jax_cache"
    # the same path on every call: never a temp name, pid or time
    assert compile_cache.compile_cache_dir() == compile_cache.REPO_CACHE
