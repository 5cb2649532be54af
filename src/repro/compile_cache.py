"""JAX's persistent compilation cache, kept at one fixed place.

Entry points call ``enable()`` from their ``main()`` (never at import):

- when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as its
  cache directory, and this module sets no other;
- otherwise the cache lives in ``<checkout>/.jax_cache`` (gitignored). The
  path is part of each entry's key, so it is fixed: never built from a
  temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``src/repro/compile_cache.py`` -> the checkout root.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
