"""Persistent compilation cache shared by the repository's entry points.

Every script (``chip_smoke.py``, ``examples/*.py``, ``benchmarks/run.py``)
calls :func:`enable_compile_cache` at startup, so repeated runs reuse
compiled kernels and steps.  The cache's path is part of its key, so it
never moves: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself and nothing here overrides it), else
``<repo>/.jax_cache``.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
