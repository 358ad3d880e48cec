"""JAX's persistent compilation cache, kept at one fixed path.

The cache key includes the directory, so a path built from a temp name, a
pid or the time never hits.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
honoured as it stands; otherwise the cache lives in ``.jax_cache/`` at the
root of the checkout, which ``.gitignore`` lists.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root: src/repro/launch/ -> three levels up
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def cache_dir() -> str:
    """The directory the cache uses: ``$JAX_COMPILATION_CACHE_DIR`` if set,
    else ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV) or os.path.join(_ROOT, ".jax_cache")


def enable() -> str:
    """Turn the cache on at :func:`cache_dir` and return that path."""
    import jax
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
