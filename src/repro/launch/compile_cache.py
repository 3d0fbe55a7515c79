"""Persistent XLA compile cache at a place the caller can control.

``use_compile_cache()`` is the first call in every entry point's ``main()``
(never at import, so tests see JAX's defaults).  Where the environment sets
``JAX_COMPILATION_CACHE_DIR`` JAX already uses that directory and nothing is
set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``, a path
fixed by this file's location, so the next process in the same checkout
finds it again (a directory named after a pid, a temp name or the time
would never be looked in twice).
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
