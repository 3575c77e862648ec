"""JAX's persistent compilation cache, placed from outside or at a
fixed path in the checkout.

Entry points call :func:`configure` at the start of ``main`` — never at
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing here overrides it.  Otherwise the cache lives at
``<repo root>/.jax_cache``: a fixed path, because the path is part of
the cache key, so a directory that moves (a temporary name, a process
id, a date) never hits.  Nothing is created until JAX writes an entry.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Point the cache at the fixed in-checkout directory unless the
    environment already chose one; returns the directory in charge."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
