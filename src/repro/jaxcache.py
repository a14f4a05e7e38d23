"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` once, at start-up; nothing
calls it at import.  ``JAX_COMPILATION_CACHE_DIR``, when set, already
configures JAX and wins.  Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path, because the path is part of what a
later process must find again.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
