"""Where JAX keeps its persistent compilation cache.

An olmo-1b prefill, decode step and one admission program per padded
prompt width compile in tens of seconds each on a TPU; the persistent
cache lets the next process on the same machine skip them. The cache
key includes the directory, so the directory must not move between
runs: no temp name, pid or time in it.

Entry points call :func:`enable_compile_cache` from ``main()``; importing
this module changes nothing.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed; otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
