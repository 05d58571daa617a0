"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, the examples, the benchmarks) call
:func:`enable` once, before their first compile.  No package module calls it
while it is imported, so tests and library users keep JAX's own settings.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/compile_cache.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[2]


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads that
    directory and nothing else is set.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, because a later run finds an
    entry again only where the first one wrote it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # The GNN programs compile in well under JAX's default one-second floor
    # for caching; keep every one of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
