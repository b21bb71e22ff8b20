"""JAX's persistent compilation cache, kept at one fixed place.

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
else is set here. Otherwise the cache lives at ``<checkout>/.jax_cache``
(listed in ``.gitignore``): a fixed path, so that a later process finds what
an earlier one compiled — a directory named after a temporary name, a
process id or the time would never be found again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: the checkout's own cache directory (src/repro/launch/cache.py -> checkout)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
