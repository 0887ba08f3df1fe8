"""Where compiled programs persist between runs.

JAX reads `JAX_COMPILATION_CACHE_DIR` itself; when it is set, nothing
here overrides it. Otherwise the entry points (`chip_smoke.py`,
`launch.serve.main`, the benchmark scripts) call `use_checkout_cache()`,
which keeps the cache at one fixed directory of the checkout,
`.jax_cache/` (git-ignored). The directory is part of what a cached
entry is found by, so it never moves. Library code never calls this:
tests must not inherit a cache.
"""
from __future__ import annotations

import os

CHECKOUT_CACHE = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache"))


def use_checkout_cache() -> str:
    """Point JAX's persistent compilation cache at the checkout's
    `.jax_cache/` unless `JAX_COMPILATION_CACHE_DIR` already places it.
    Returns the directory in use."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
