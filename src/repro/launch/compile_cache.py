"""Where compiled XLA programs persist between processes.

A full-width step compiles for tens of seconds; the persistent cache lets
the next process on the same machine skip that.  The cache key includes
the directory, so the path must not move between runs: it is fixed to
``<checkout>/.jax_cache`` (listed in ``.gitignore``) unless the
environment names one in ``JAX_COMPILATION_CACHE_DIR``, which JAX reads
itself.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
