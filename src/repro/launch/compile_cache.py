"""One fixed home for JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks.run``, ``repro.launch.
kg_serve``) call :func:`configure_compile_cache` first thing; importing
``repro`` never does, so library users and the tests keep JAX's own
default. The cache key includes the directory, so a directory that moves
between runs never hits: the path is fixed, never temporary, per-process
or time-derived.
"""
from __future__ import annotations

import os

#: the checkout this module lives in (``<checkout>/src/repro/launch``)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def configure_compile_cache() -> str:
    """Place the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set. Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
