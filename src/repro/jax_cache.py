"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``bench/run.py``) call
:func:`use_compile_cache` once, before their first compile.  Library
modules never call it when imported, and tests never call it.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["use_compile_cache"]

_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Point the persistent compile cache at a fixed directory; return it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads it itself.
    Otherwise the cache lives at ``<checkout>/.jax_cache`` (git ignores
    it): a fixed path, since the path is part of the cache key.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
