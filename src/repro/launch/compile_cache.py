"""Where JAX keeps its persistent compilation cache.

One call, made by an entry point's ``main()`` (never at import):

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself;
  nothing is set in code, so a cache placed from outside is the one
  used.
* unset — the cache lives at ``<checkout>/.jax_cache`` (git-ignored).
  The path is fixed: it is part of the cache key, so a directory named
  from a temp dir, a pid or the time would never hit.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

CHECKOUT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one directory and
    return that directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
