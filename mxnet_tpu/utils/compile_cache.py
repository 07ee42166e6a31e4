"""The one rule for JAX's persistent compilation cache.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
program sets no directory in code.  Otherwise the cache is
``<checkout>/.jax_cache`` — a fixed path, because the path is part of
the cache key: a directory named after a pid, a time or a temp name
never hits.  An entry point that wants warm restarts (chip_smoke.py)
calls :func:`enable` once, before its first compile;
benchmarks/run.py takes the environment branch: it sets the variable to
the same directory before it imports jax."""
from __future__ import annotations

import os

from .libloader import repo_root


def enable():
    """Turn the persistent compile cache on; returns the directory."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(repo_root(), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir
