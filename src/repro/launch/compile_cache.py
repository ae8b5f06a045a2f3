"""Where the entry points keep JAX's persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is unset, the
entry points call :func:`setup_compile_cache` at start-up so that runs
from one checkout share a cache at a fixed path (the path is part of
the cache key: a directory that moves never hits). Nothing else in the
package sets a cache path.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's compilation cache at ``<checkout>/.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` names a directory; return the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
