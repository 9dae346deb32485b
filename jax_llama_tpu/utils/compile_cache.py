"""Where JAX's persistent compilation cache lives — placed from outside.

Called by the entry points (``run.py`` ``main``, ``chip_smoke.py``'s
children, ``rehearsal.py``), never at package import and never by
the tests.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and this module sets nothing in code; otherwise the cache goes to
``<checkout>/.jax_cache``.  The path is part of the cache key, so it is
derived from this file's own location and nothing that changes between
runs: every process of one checkout shares one cache.
"""

from __future__ import annotations

import os
from pathlib import Path

_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """The cache directory this checkout uses (imports no jax)."""
    env = os.environ.get(_ENV)
    if env:
        return env
    return str(Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
