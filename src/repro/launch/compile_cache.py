"""Persistent XLA compilation cache for the entry points.

Called from the ``main()`` of each launcher and of ``chip_smoke.py``; never at
import and never from tests.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: used when JAX_COMPILATION_CACHE_DIR is unset. The path is part of the
#: cache key, so it is fixed to the checkout rather than to a temp dir.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    JAX reads JAX_COMPILATION_CACHE_DIR itself; where it is set, no other
    directory is set here.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
