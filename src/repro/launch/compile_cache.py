"""JAX's persistent compilation cache, in one place.

A full-width serve or train step takes tens of seconds to compile, and every
fresh process compiles it again unless the executable is on disk.  The cache
path is part of the cache's key, so it must not move between runs: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set (and then nothing else is
chosen), otherwise ``<checkout>/.jax_cache`` — never a temporary, per-pid or
per-run name.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (this file is <checkout>/src/repro/launch/...)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Call before the first compile."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # a process that already compiled has decided "no cache"; make it
        # look again at the new directory
        compilation_cache.reset_cache()
    return path
