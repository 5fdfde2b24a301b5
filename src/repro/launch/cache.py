"""JAX's persistent compilation cache for the launchers.

Call :func:`setup_compile_cache` once, before the first compile, from a
program's entry point (``repro.launch.train.main``, ``chip_smoke.py``);
importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

#: fixed cache directory inside the checkout (git ignores it). The
#: path is part of the cache key, so it never moves.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on the persistent compile cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here. Otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
