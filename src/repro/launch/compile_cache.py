"""JAX's persistent compilation cache for the program's entry points.

A serving process compiles one program per (Q bucket, width) and per
kernel; on a TPU that is seconds to minutes of start-up. With the cache on,
a later process reuses what an earlier one compiled.
"""
from __future__ import annotations

import os

import jax

#: fixed in-checkout location, used when the environment names none
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to ``<checkout>/.jax_cache``
    — a fixed path, so every later process of this checkout finds it again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
