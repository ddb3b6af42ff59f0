"""JAX's persistent compilation cache, kept at one fixed path.

Every entry point calls ``enable_compile_cache()`` at the start of its
``main()`` (never at import), before anything compiles.  The cache's path is
part of its key, so it must not move between runs: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here; otherwise the cache lives in ``<repo>/.jax_cache`` (gitignored).
"""
from __future__ import annotations

import os

import jax

#: the repository root: src/repro/launch/ -> three levels up
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
