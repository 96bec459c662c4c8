"""JAX's persistent compilation cache for the repo's entry points.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
module sets no directory.  Otherwise the cache lives at `CACHE_DIR`, one
fixed directory inside the checkout: the directory is part of the cache
key, so a path that moved between runs would never hit.

Either way the key includes the programs' metadata: keyed without it, an
executable loaded from the cache keeps the op names of whichever source
compiled it first, and a profiler trace would name ops by the scopes
(DESIGN.md §14) of other code than the code that runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
