"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR`` places the cache from outside (JAX reads it
itself); without it the cache lives at one fixed path inside the checkout,
which ``.gitignore`` lists, so that a rerun from the same checkout finds
what the last run compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Sets nothing when ``JAX_COMPILATION_CACHE_DIR`` is set, so the
    environment's directory is the only one in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
