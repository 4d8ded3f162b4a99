"""One place for XLA's persistent compile cache.

The server compiles one program per model and per shape (tens of seconds
for the vision model alone), and every fresh process pays that again
unless the compiled programs are kept. The directory is part of the
cache's key, so it must not move between runs: it is the one JAX is told
through ``JAX_COMPILATION_CACHE_DIR``, or else one fixed directory inside
the checkout — never a temporary name, a pid or a time.
"""

from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
    set here. Call before the first compile."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
