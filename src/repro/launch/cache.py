"""Persistent XLA compilation cache for the repo's entry points.

Only entry points call :func:`enable_compile_cache` (``chip_smoke.py``,
``benchmarks/run.py``, the examples, the ``repro.serve.drill`` CLI);
importing the library never touches the cache, so tests and embedding
applications keep JAX's own defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path: the cache is keyed on the program, and a directory that
# moved between runs (a temp or pid-derived one) would never be hit
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across processes and return the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    no other directory is set here; otherwise the cache lives in
    ``<repo>/.jax_cache``.  Every program is cached, not only those that
    took a second to compile: a solve compiles many small setup
    programs, and on a cold chip they add up."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
