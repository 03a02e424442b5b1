"""JAX's persistent compilation cache for the repository's entry points.

Programs compiled by one run are found again by the next one only if the
cache sits at the same path, since the path is part of the cache's key. So
the directory is ``JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise one fixed directory inside the checkout (``.jax_cache``, which
``.gitignore`` lists) — never a temporary, per-process or dated name.

Call :func:`enable_compile_cache` from a script's ``main``; importing this
module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout's root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that
    directory from the environment and no other is set here. Every
    program is cached, not only those that took a second to compile: the
    serving path is many small programs, and a cold start pays for each.
    """
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
