"""JAX's persistent compilation cache, placed by the entry points.

The scripts a user runs (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable` before their first compile; nothing sets the cache on
library import or in tests. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already keeps its cache there and this module sets no other.
Otherwise the cache goes to the fixed ``<checkout>/.jax_cache``: the
directory is part of the cache key, so a path that changes from run to
run (a temp name, a pid, a time) would never hit.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def cache_dir(checkout: str) -> str:
    """Where the cache lives: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV) or os.path.join(os.path.abspath(checkout),
                                               ".jax_cache")


def enable(checkout: str) -> str:
    """Turn the persistent cache on for this process (see the module
    docstring) and return its directory."""
    path = cache_dir(checkout)
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
