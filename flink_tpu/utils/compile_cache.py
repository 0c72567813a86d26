"""Placement of JAX's persistent compilation cache.

One rule for every process that builds device programs: where
`JAX_COMPILATION_CACHE_DIR` is set, jax reads it itself and nothing is set
in code; otherwise the cache lives at `<checkout>/.jax_cache`. The path is
part of the cache key, so it is never derived from a temp name, a pid or
the time.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Call before the process compiles its first device program. Returns
    the directory the cache is kept in."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir
