"""JAX's persistent compilation cache, kept at one fixed place.

A cold run on a TPU compiles every step program again (one per prompt-length
shape for the serve path), so the launchers keep compiled programs on disk.
The cache key includes the directory, so the directory never moves: it is
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself), and ``<checkout>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    Sets nothing when ``JAX_COMPILATION_CACHE_DIR`` is set."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
