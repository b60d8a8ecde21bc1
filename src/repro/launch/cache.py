"""Where the entry points keep JAX's persistent compilation cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
nothing here overrides it.  Otherwise the cache lives at one fixed path
inside the checkout: the path is part of what JAX keys an entry on, so a
directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the in-checkout cache directory (listed in .gitignore)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
