"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so a directory that moves never hits.
Every process that compiles for the device (the store, ``chip_smoke.py``, the
benchmark) calls :func:`place_compile_cache` before its first compile and
gets the same answer: the directory ``JAX_COMPILATION_CACHE_DIR`` names when
the caller's environment sets it (JAX reads that variable itself, so nothing
is set in code), else ``<checkout>/.jax_cache``, where every compiled program
is kept whatever its compile time.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def place_compile_cache() -> str:
    """Returns the directory compiled programs are kept in."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # keep every program: at JAX's one-second floor a compile that took 0.9 s
    # in one run and 1.1 s in the next makes the second run add entries
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_DIR
