"""JAX's persistent compilation cache at a fixed place.

Called by the entry points (``launch/serve.py``, ``launch/train.py`` and
``chip_smoke.py``) before their first compile, never at import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
path, because the path is part of the cache key, so a directory that moves
never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
