"""Persistent XLA compilation cache shared by every entry point.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
module sets nothing.  Otherwise the cache lives at one fixed directory in
the checkout, ``.jax_cache/`` (git-ignored): the path is part of what a
later run must find again, so it never holds a temp name, a pid or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
