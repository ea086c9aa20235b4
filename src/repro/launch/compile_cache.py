"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``launch/``, ``examples/``,
``benchmarks/``) call ``use_compile_cache()`` first thing in ``main`` —
never at import — so a second run skips the compiles the first one paid for.
The cache key includes the directory, so the directory is fixed: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing more
is set; otherwise the cache lives at ``<checkout>/.jax_cache`` (ignored by
git). It never comes from a temporary directory, a pid or the clock.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
