"""JAX's persistent compilation cache, placed from outside the library.

The engine compiles many small stage programs (one per query shape and
capacity class), so time-to-first-answer is dominated by compilation; a
persistent cache lets the next process read them back.  Entry points call
:func:`enable_compile_cache` once at start-up — never at import, so a
library user keeps control of JAX's configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "CHECKOUT_CACHE"]

# <checkout>/.jax_cache — a fixed path, because the directory is part of
# the cache key; listed in .gitignore
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set; otherwise the cache lives in ``<checkout>/
    .jax_cache``.  Every program is cached, however fast it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
