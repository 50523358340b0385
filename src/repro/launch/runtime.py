"""Process set-up shared by the entry points (serve, train, chip_smoke.py).

Nothing here runs at import: each entry point calls ``use_compile_cache``
once, before its first compile. Library code and tests never call it.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and no
    other directory is set here. Otherwise the cache lives at the fixed
    ``.jax_cache/`` of the checkout: the path is part of the cache key, so
    a directory that moved between runs would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
