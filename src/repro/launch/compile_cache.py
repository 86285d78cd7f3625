"""Where JAX's persistent compile cache lives, for the program's entry
points (`chip_smoke.py`, the benchmarks).  Call `enable_compile_cache()`
from an entry point before the first compile; importing `repro` never
touches the cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# A fixed path inside the checkout (git-ignored): a directory named from a
# pid, a time or a temp dir is new on every run, so nothing cached there
# would be found again.
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> pathlib.Path:
    """Turn on the persistent compile cache and return its directory.

    If `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and this
    sets nothing.  Otherwise the cache goes to `.jax_cache` at the root of
    the checkout.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return pathlib.Path(env)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return CHECKOUT_CACHE


__all__ = ["enable_compile_cache", "CHECKOUT_CACHE"]
