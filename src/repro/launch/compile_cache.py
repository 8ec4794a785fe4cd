"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``, ``benchmarks.run``) calls
:func:`enable_compile_cache` once, before it compiles anything.
"""
from __future__ import annotations

import os

import jax

#: the root of the checkout (this file is src/repro/launch/compile_cache.py)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: JAX
    reads it, and nothing here overrides it.  Otherwise the cache is
    ``.jax_cache/`` at the root of the checkout (listed in ``.gitignore``):
    a fixed path, so a later process in the same checkout finds what this
    one compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
