"""Placement of JAX's persistent compilation cache for the entry points.

The cache's directory is part of every entry's key, so a directory that
moves between runs never hits. ``JAX_COMPILATION_CACHE_DIR``, when set, is
JAX's own setting and wins; otherwise the cache lives at one fixed path
inside the checkout (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile
    and return its directory: ``JAX_COMPILATION_CACHE_DIR`` if set (JAX
    reads it itself; nothing is set here), else ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
