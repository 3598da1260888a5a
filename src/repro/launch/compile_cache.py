"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only under the directory it was
written to, so the directory must not move between runs.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this module sets
nothing; otherwise the cache lives in ``.jax_cache`` at the root of the
checkout (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory: <checkout>/.jax_cache
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
