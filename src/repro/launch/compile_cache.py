"""JAX's persistent compilation cache, kept at one fixed place.

The directory is part of every cache key, so it never moves between
runs: ``$JAX_COMPILATION_CACHE_DIR`` when that is set, otherwise
``<checkout>/.jax_cache`` (listed in ``.gitignore``). Entry points call
:func:`enable_compilation_cache` first thing; importing this module
changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    and return that directory."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
