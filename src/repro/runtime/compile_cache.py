"""JAX's persistent compilation cache at one fixed place.

Entry points call :func:`enable_compile_cache` before their first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
it and this sets no other directory; otherwise the cache lives at
``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The path is part
of each entry's key, so it is fixed: never derived from a temp name, a
pid or the time.

Op names, which carry the program's scopes (:mod:`repro.runtime.tracing`),
live in the IR's debug information, and JAX leaves that out of a cache
entry's key by default: an executable loaded from the cache would then
carry the op names of whatever program first filled the entry, and a
profile would show stale scopes or none.  So the key includes it, with
source paths taken relative to the checkout, so that an entry still
holds wherever the checkout lies.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        "^" + re.escape(f"{CHECKOUT}{os.sep}"),
    )
    return path
