"""JAX's persistent compilation cache for the launchers.

A function, called from each launcher's ``main()`` and from
``chip_smoke.py``, never at import: importing the package must not
change JAX's configuration for tests or library users.
"""
from __future__ import annotations

import os

import jax

# the checkout root: src/repro/launch/cache.py -> three levels up from src
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at a fixed path in the
    checkout (``.jax_cache/``, git-ignored): the path is part of each
    entry's key, so a directory derived from a temp name, pid or time
    would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
