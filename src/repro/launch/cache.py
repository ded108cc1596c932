"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the directory, so a path built from a temporary
name, a process id or the time never hits again.  Call
:func:`enable_compile_cache` before the first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(checkout) -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``.
    """
    import jax

    path = os.environ.get(ENV)
    if path:
        return path
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
