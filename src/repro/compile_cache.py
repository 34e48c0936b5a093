"""Persistent compilation cache for the program's entry points.

A fresh process compiles every device program from scratch; JAX's
persistent cache keeps the compiled executables on disk, so a second run of
an entry point in the same checkout skips most of that.  The directory is
part of what a later run looks up, so it is a fixed path:
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself), and
``<checkout>/.jax_cache`` otherwise.

Entry points (``chip_smoke.py``, ``examples/*``) call
``enable_compile_cache()`` before they compile anything.  Importing
``repro`` never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
