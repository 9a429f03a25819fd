"""JAX's persistent compilation cache for the repo's entry points.

Every script that drives the chip calls ``configure_compile_cache()``
before its first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and nothing else is set here.  Otherwise the cache
lives at one fixed directory inside the checkout, ``<repo>/.jax_cache``
(git-ignored): the cache directory is part of what makes an entry found
again, so it is never derived from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache")
)


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
