"""JAX's persistent compilation cache, placed for the entry points.

Programs call :func:`enable_compile_cache` from their ``main``; no library
module calls it when imported. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX keeps its cache there and this sets nothing. Otherwise the cache goes to
``.jax_cache/`` at the root of the checkout: a fixed path, since the path is
part of what a later run has to find again. A hit shortens a cold
invocation's ``gpu_ctx`` stage, which on a TPU is an XLA compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
