"""Persistent XLA compilation cache for the entry points.

A full-width step compiles for tens of seconds on the chip; the cache
lets a second run of ``chip_smoke.py`` or a launcher on the same machine
reuse it.  The cache key includes the directory, so it must not move
between runs.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; call before the first
    compile.  Returns the cache directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    this leaves JAX's setting alone.  Otherwise the cache lives in the
    fixed, git-ignored ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
