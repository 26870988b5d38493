"""Where JAX's persistent compilation cache lives, for every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, is in charge: JAX reads it
itself and nothing here overrides it.  Otherwise the cache goes to a
fixed ``<checkout>/.jax_cache`` — a fixed path, because the cache key
includes it, so a directory that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one place and
    return that directory.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
