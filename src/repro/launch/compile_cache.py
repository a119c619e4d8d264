"""Persistent XLA compilation cache for the serving entry points.

A full-width step compiles once per row/chunk bucket, so a cold start can be
mostly compile time. Every entry point calls :func:`enable_compile_cache`
before its first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and the cache lives
  there; no other directory is configured.
* unset: the cache goes to ``.jax_cache/`` at the root of the checkout. The
  path is part of what a later run must find again, so it is fixed (never
  derived from a temp name, a pid or the time).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
