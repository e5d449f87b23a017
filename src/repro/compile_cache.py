"""Persistent XLA compilation cache for the command-line entry points.

A cold process compiles every kernel and jitted step again; JAX's
persistent cache lets a later process load them instead.  A later
process finds an entry only if it looks in the directory an earlier one
wrote, so the directory is fixed, never a temp, pid or time name: the
one ``JAX_COMPILATION_CACHE_DIR`` names when it is set, else
``.jax_cache/`` at the checkout root.  Entry points call :func:`enable` from ``main``;
nothing enables it at import, so library users and tests keep JAX's
own defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    JAX already reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when that
    is set no other directory is configured.  Every executable is
    cached, however fast it compiled.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
