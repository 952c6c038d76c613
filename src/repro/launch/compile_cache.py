"""Where JAX keeps its persistent compile cache for the entry points.

The cache key includes the directory, so the directory must not move
between runs: it is either what ``JAX_COMPILATION_CACHE_DIR`` names (JAX
reads that variable itself, and nothing is changed here) or the fixed
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
