"""JAX's persistent compilation cache at one fixed place.

Entry points call :func:`enable_compile_cache` before their first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
it and this sets no other directory; otherwise the cache lives at
``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The path is part
of each entry's key, so it is fixed: never derived from a temp name, a
pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
