"""Persistent XLA compilation cache for the program's entry points.

A full-width round step takes minutes to compile, so every command-line
entry point (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``, ``python -m repro.api``, ``benchmarks.run``)
calls :func:`enable_compile_cache` before its first compile.  Library
imports and tests never do: they leave JAX's configuration alone.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout's own cache: fixed, so the next run finds its entries
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives in
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
