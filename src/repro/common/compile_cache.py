"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the ``examples/``
mains) call :func:`use_compile_cache` once at start-up; nothing calls it at
import. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this helper sets nothing. Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path, since the directory is part of what a
later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
