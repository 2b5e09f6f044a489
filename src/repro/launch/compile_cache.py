"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points call :func:`enable_compile_cache` once at start-up; importing
this module changes nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and the helper sets nothing: whoever runs the program
decides where the cache lives.  Otherwise the cache goes to ``.jax_cache/``
at the checkout root.  The path is part of each entry's key, so it is fixed:
never made from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os

import jax

CHECKOUT_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
