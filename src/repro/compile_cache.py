"""JAX's persistent compilation cache, in one place.

Entry points that compile for a device call :func:`enable_compile_cache`
once, before their first compile; importing this module does nothing.
"""
from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]

# The cache key includes the directory, so it must not move between runs:
# a fixed path inside the checkout, never one built from a temporary
# name, a pid or the time.
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    this sets nothing; otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
