"""Where JAX keeps its persistent compilation cache — set in one place.

``JAX_COMPILATION_CACHE_DIR``, where set, is read by JAX itself and this
sets nothing.  Otherwise the cache lives at a fixed ``<checkout>/.jax_cache``
(listed in ``.gitignore``): the directory is part of the cache key, so a
path that moved between runs would never hit.
"""
import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.  Call
    before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
