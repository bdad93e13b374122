"""Where compiled XLA programs persist between processes.

A full-width step compiles for tens of seconds; the persistent cache lets
the next process on the same machine skip that.  The cache key includes
the directory, so the path must not move between runs: it is fixed to
``<checkout>/.jax_cache`` (listed in ``.gitignore``) unless the
environment names one in ``JAX_COMPILATION_CACHE_DIR``, which JAX reads
itself.

The key includes each instruction's metadata: without it a program that
differs from a cached one only in its ``op_name`` scopes (the kernel sites
of ``repro.models.compute``) is served the cached executable, whose
profile then names the old scopes.  The metadata holds the file and line
of each frame that traced the program, so an entry is found again from
the same entry point and unchanged files only.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
