"""JAX's persistent compilation cache at a fixed place.

The path is part of what the cache finds again, so it never moves: the
directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set, otherwise
``.jax_cache`` at the root of the checkout.
Entry points call ``use_compile_cache()`` before they compile anything.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its fixed directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
