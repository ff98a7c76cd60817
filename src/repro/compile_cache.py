"""JAX persistent compilation cache for the entry points.

Every ladder width, fused-episode shape and kernel compiles on first
use; a process that starts cold (a benchmark run, the chip smoke check)
pays all of it again unless compiled programs persist on disk.
:func:`enable` turns the cache on.  It is called by the entry points at
start-up and never as a side effect of importing a module.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: a fixed path, because the directory is part of
# the cache key (a moving directory never hits); listed in .gitignore
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself); otherwise the cache lives at :data:`DEFAULT_DIR`.  Every
    compile is cached, however short, since a cold process repeats them
    all."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
