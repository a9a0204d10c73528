"""Where compiled programs persist between runs.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` from the environment; where it is
set, that directory holds the cache and nothing here overrides it.  Where
it is unset, the cache lives in ``.jax_cache/`` at the repository root.
The path is fixed (never a temp name, a pid or the time): it is part of
each entry's key, so a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Call once, before the first compile: JAX
    fixes the cache location when it first compiles."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
