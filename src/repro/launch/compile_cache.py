"""JAX's persistent compilation cache, placed from outside the library.

The entry points (``gpgpu_serve.main``, ``chip_smoke.py``) call
:func:`enable_compile_cache` once, before their first compile;
importing the library never touches the cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
cache lives there: this module sets no other directory.  Otherwise the
cache goes to one fixed path inside the checkout, :data:`DEFAULT_DIR`.
The path is part of what a later run must find again, so it never
comes from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` — this file is ``src/repro/launch/...``
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its
    directory.  Every compiled program is kept, however quickly it
    compiled: a serving run compiles many small shape buckets, and a
    restart should find all of them."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
