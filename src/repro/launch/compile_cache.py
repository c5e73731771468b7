"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the cache directory, so a directory that moves
(a temp name, a process id, a timestamp) never hits.  Entry points call
:func:`enable_compile_cache` once before they compile; library code and
tests never turn it on.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout this module was imported from (``src/repro/launch/..``)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is
    left to JAX and nothing is set in code.  Otherwise the cache lives in
    ``<checkout>/.jax_cache`` (git-ignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
