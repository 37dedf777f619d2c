"""Persistent XLA compilation cache for the entry points.

A chip call starts with no compiled code, and the cache key includes
the directory, so the directory must not move between runs: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself)
and otherwise the fixed ``<repo>/.jax_cache`` (gitignored). Entry points
call ``enable`` from ``main()``; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable(repo_root) -> str:
    """Point JAX's persistent compilation cache at the directory above;
    returns it."""
    env = os.environ.get(_ENV)
    if env:
        return env
    import jax
    path = str(Path(repo_root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
