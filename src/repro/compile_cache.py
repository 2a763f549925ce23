"""JAX's persistent compilation cache, kept at one fixed place.

A run on a fresh machine compiles every program it uses; the persistent
cache lets a later process with the same programs load them instead.  The
directory must not move between runs, or nothing is ever found again, so
it is never a temporary, per-process or time-stamped path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout this module was loaded from (src/repro/ -> repo root)
CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is configured here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache`` (listed in .gitignore)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
