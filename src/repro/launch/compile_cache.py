"""Persistent compilation cache location, shared by every entry point.

JAX keys cache entries by their directory, so a directory that moves never
hits: the cache lives where ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads
that variable itself, and nothing here overrides it), else at the fixed
``<repo>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
