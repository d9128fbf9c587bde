"""JAX's persistent compilation cache for the repo's entry points.

A cold round at the paper CNN's update size spends most of its first
call compiling.  Entry points (``chip_smoke.py``, ``examples/``,
``benchmarks/``) call :func:`enable_compile_cache` before their first
jit, so a second process on the same machine reuses the executables.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
this module sets nothing.  Otherwise the cache lives at a fixed
``.jax_cache/`` under the repository root: the path is part of the
cache's key, so it is never derived from a temp name, a pid or the
time.  Tests never call it, so the test suite runs with no cache.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> Optional[Path]:
    """The directory this process should set, or None where the
    environment already names one (JAX reads ``ENV_VAR`` itself)."""
    if os.environ.get(ENV_VAR):
        return None
    return REPO_CACHE


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = compile_cache_dir()
    if path is None:
        return os.environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
