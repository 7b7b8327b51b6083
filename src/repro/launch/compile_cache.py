"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`enable_compile_cache` once, before they compile
anything.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that
variable itself and the helper leaves it alone; otherwise the cache goes
to one fixed directory inside the checkout (:data:`DEFAULT_DIR`, ignored
by git).  That path is never built from a temporary name, a process id
or the time, so a later run from the same checkout finds what an earlier
one stored.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

#: cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    placed = os.environ.get(ENV_CACHE_DIR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
