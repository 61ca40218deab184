"""JAX's persistent compilation cache, for the repository's entry points.

A process that runs the query path on a chip compiles every kernel and
jitted step it meets, and a cold compile can take a large share of a short
run.  The persistent cache keeps those compiles across processes.

Call :func:`enable_compile_cache` once from an entry point — ``chip_smoke.py``,
``benchmarks/run.py``, the examples — before the first compile.  Library
code and the tests never turn the cache on.
"""
from __future__ import annotations

import os

#: Cache directory inside the checkout when the environment names none.
CACHE_DIRNAME = ".jax_cache"

#: Root of the checkout this package is imported from (``<root>/src/repro``).
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    in that directory and no other is set here.  Otherwise the cache lives
    at ``<checkout>/.jax_cache``: a fixed path, so a later run from the same
    checkout finds what an earlier one compiled.  Every compile is kept,
    however short (the kernels compile in about a second each).
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, CACHE_DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
