"""Where the entry points keep JAX's persistent compilation cache.

A deployment that sets ``JAX_COMPILATION_CACHE_DIR`` owns the placement:
JAX reads the variable itself and nothing here overrides it.  Otherwise
the cache lives at a fixed ``<checkout>/.jax_cache`` (git-ignored) — the
path is part of the cache's key, so a temporary, per-process or dated
directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT / ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir`.

    Call once at an entry point, before the first compile; returns the
    directory.
    """
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
