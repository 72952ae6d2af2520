"""JAX's persistent compilation cache for the entry points.

Compiling the engine's programs for a TPU takes tens of seconds each, so
the entry points keep compiled programs on disk. ``JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself); otherwise the cache goes to a
fixed directory of the checkout, so that the path — part of the cache key —
never moves. Called from entry points only, never at import: tests keep
JAX's defaults.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use:
    ``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache`` at the root of the
    checkout this package was imported from."""
    d = os.environ.get(ENV)
    if d:
        return d
    import jax
    here = os.path.dirname(os.path.abspath(__file__))
    d = os.path.abspath(os.path.join(here, "..", "..", "..", ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", d)
    return d
