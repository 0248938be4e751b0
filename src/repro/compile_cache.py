"""Where JAX keeps its persistent compilation cache.

One rule for every entry point that compiles for the chip: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it; otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The path is part
of the cache key, so it is never temporary, per-process or time-stamped.
"""
from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` — this file sits at ``<checkout>/src/repro/``
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
