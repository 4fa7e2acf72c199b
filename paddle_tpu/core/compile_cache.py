"""Where generated state lives: the checkout, and JAX's persistent
compilation cache inside it.

A cold compile of the train step or of the serving programs is most of a
first run on the chip; a later process finds it again only if the cache
directory is the same one — the path is part of the cache key, so it is
never made from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os

# root of the checkout that holds this package
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Place the compilation cache; call before the first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set in code (the machine's operator placed it);
    otherwise the cache is ``<checkout>/.jax_cache`` (in ``.gitignore``).
    Returns the directory in use. Touches no backend."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
