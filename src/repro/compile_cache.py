"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
call :func:`setup_compile_cache` before their first ``jit``, so every
process of this repository shares one cache:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing else is
  set in code.
* unset: ``<repo>/.jax_cache``, a fixed path (git-ignored). The path is
  part of the cache key, so it never depends on a temp dir, pid or time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["setup_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
