"""JAX's persistent compilation cache, placed for the entry points.

``chip_smoke.py`` and the benchmark and example ``main`` functions call
:func:`enable_compile_cache` before their first compile, so a later run
on the same machine reloads the simjoin kernels instead of compiling
every ``(na, nb)`` bucket and pair-list length again. Library imports
and the tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

#: Where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path in the checkout (git ignores it). The path is part of the
#: cache's key, so it must not move between runs.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set (and no other), else
    :data:`DEFAULT_DIR`. Every compile is kept, however short: a
    simjoin kernel compiles in about a second, below JAX's default
    threshold."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
