"""JAX's persistent compilation cache, placed from outside the program.

Every process that serves or checks the model (``chip_smoke.py``,
``python -m repro.launch.serve``, each supervised worker) calls
:func:`enable_compile_cache` before its first compile, so a later run of
the same checkout loads the compiled buckets instead of compiling them.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

# .../src/repro/serving/compile_cache.py -> the checkout root
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of the
    variable already puts the cache there and no directory is set here.
    Otherwise the cache is ``.jax_cache/`` at the checkout root: a fixed
    path, because the path is part of what a later run must find.  Every
    compile is kept, however short: a bucket ladder's sub-second compiles
    add up to a restart's set-up time like the long ones.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    d = os.environ.get(ENV)
    if d:
        return d
    d = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    return d
