"""JAX's persistent compilation cache, kept at one fixed place, and a
count of the executables the process builds or loads.

The cache key includes the cache directory, so a directory that moves
(a temp name, a process id, a timestamp) never hits.  Entry points call
:func:`enable_compile_cache` once before they compile; library code and
tests never turn it on.

:func:`compiles` counts every backend compile of the process, a load
from the persistent cache included (JAX records one
``/jax/core/compile/backend_compile_duration`` event around each
``compile_or_get_cached``).  :func:`dispatch_span` wraps a compiled call
in a profiler span and marks the calls that compiled.
"""
from __future__ import annotations

import contextlib
import os
import pathlib
import re

import jax

#: the checkout this module was imported from (``src/repro/launch/..``)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is
    left to JAX and no other directory is set in code.  Otherwise the
    cache lives in ``<checkout>/.jax_cache`` (git-ignored).

    The cache key includes each op's metadata (name scopes, source
    lines).  Without it a program that differs from a cached one only in
    its names loads the cached executable, and a profiler trace then
    names the device ops as that other program did.  Source files are
    named from the checkout's root, so the key does not depend on where
    the checkout lies.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(CHECKOUT) + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiled = 0


def _on_duration(event: str, duration: float, **kwargs) -> None:
    global _compiled
    if event == _COMPILE_EVENT:
        _compiled += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compiles() -> int:
    """Executables this process has built or loaded so far.  After
    warm-up a serving or training loop should stop adding to it."""
    return _compiled


@contextlib.contextmanager
def dispatch_span(name: str, marker: str, **args):
    """Profiler span ``name`` around a call into a compiled step; when
    the call compiled, the empty span ``marker`` follows it, so a trace
    shows which call compiled.  ``args`` land in both spans' arguments."""
    before = _compiled
    with jax.profiler.TraceAnnotation(name, **args):
        yield
    if _compiled != before:
        with jax.profiler.TraceAnnotation(marker, **args):
            pass
