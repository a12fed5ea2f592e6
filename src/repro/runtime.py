"""Process set-up shared by every entry point (chip smoke, benchmarks,
examples)."""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache — one fixed path, listed in .gitignore. The path
# is part of what a cached executable is found under, so it never carries
# a temp name, a pid or a time.
_CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at ``.jax_cache`` in
    the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)
