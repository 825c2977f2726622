"""Where JAX's persistent compilation cache lives, set once by each entry point.

A directory that changes between runs (temporary, per-pid) starts empty
each time and never hits.  ``JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it
itself and this module sets nothing.  Otherwise the cache goes to a fixed
directory inside the checkout (``<checkout>/.jax_cache``, git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the environment's
    directory, else at ``DEFAULT_DIR``; return it.  Call from ``main``,
    never at import."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
