"""The persistent XLA compile cache, for the entry points that run on the chip.

Every process on the chip otherwise recompiles every program. The entry
points (``chip_smoke.py``, ``benchmark/run.py``, ``bin/ds_serve``,
``bin/ds_replica``) call :func:`enable_compile_cache`
once, before their first compilation. It is deliberately NOT a side
effect of importing the package or of building an engine: the CPU test
suite compiles thousands of programs and must not fill the checkout.

Placement: when ``JAX_COMPILATION_CACHE_DIR`` is set JAX honours it by
itself and nothing is set here. Otherwise the cache is
``<checkout>/.jax_cache`` (git-ignored) — a fixed path, because the
directory is part of every entry's key: a temporary, pid- or
time-derived directory never hits.
"""

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; → the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
