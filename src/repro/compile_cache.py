"""JAX's persistent compilation cache, kept at one fixed place per checkout.

Call :func:`enable_compile_cache` once, before the first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is set
here. Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored), a
path that never moves, so each run finds what earlier runs cached.
"""

from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache``; this file lives at ``<checkout>/src/repro/``.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
