"""Persistent XLA compilation cache for the entry points.

Called from ``main`` of the entry points (never on import).  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing; otherwise the cache goes to a fixed directory in the checkout
(``.jax_cache/``, git-ignored) — the path is part of the cache key, so a
fixed one is what lets a later run of the same checkout hit it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on; returns the directory compiled programs land in."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
