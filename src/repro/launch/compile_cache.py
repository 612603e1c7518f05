"""Persistent compilation cache, placed from outside the program.

Call :func:`use_compile_cache` before the first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, that directory holds the cache and
the program names no other; otherwise the cache lives at a fixed path in
the checkout (``<repo>/.jax_cache``, git-ignored).  The directory is part
of the cache key, so it never depends on a temp dir, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
