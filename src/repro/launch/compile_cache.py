"""Persistent XLA compilation cache for the entry points.

Entry points call :func:`configure_compile_cache` from their ``main``
(never at import), before the first compile.  JAX keys cache entries by
the directory path, so the directory is fixed: a path that moved between
runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Return the directory compiled executables persist in.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is: JAX reads
    it itself, and nothing else is configured.  Otherwise the cache lives
    in ``.jax_cache/`` at the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
