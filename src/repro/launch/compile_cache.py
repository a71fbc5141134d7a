"""JAX's persistent compilation cache, switched on once per entry point.

Every entry point (``launch/serve.py``, ``launch/train.py``,
``chip_smoke.py``, ``benchmarks/*.py``) calls :func:`enable_compile_cache`
before its first compile. Where ``$JAX_COMPILATION_CACHE_DIR`` is set,
that is the cache and no other directory is set here. Otherwise the cache
lives at one fixed path inside the checkout, :data:`CHECKOUT_CACHE_DIR`
(git-ignored): the directory is part of what a cached program is found
by, so a temporary or per-process path would never be hit again.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` or,
    when that is unset, :data:`CHECKOUT_CACHE_DIR`; returns the directory.
    Programs are cached however fast they compile: the recurrent kernels
    each compile in well under JAX's default one-second floor, and a serve
    start compiles dozens of them."""
    path = os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
