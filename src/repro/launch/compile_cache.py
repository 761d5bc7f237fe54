"""Where the entry points keep JAX's persistent compilation cache.

The cache key includes the directory, so a path that moves between runs
never hits: the directory is ``JAX_COMPILATION_CACHE_DIR`` when that is
set (JAX reads it itself), else the fixed ``<checkout>/.jax_cache``.
Library code never calls this; entry points call it once, before their
first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the repository checkout this package was loaded from
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point the persistent cache at its directory and cache every
    program (the serving kernels compile in under a second, below JAX's
    default one-second threshold).  Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
