"""Persistent XLA compilation cache, placed from outside the program.

Every entry point that compiles kernels (the daemon, ``chip_smoke.py``,
``bench.py``, the test harness, the offline tools) calls
``enable_compile_cache()`` once before its first dispatch.  Where the
operator exported ``JAX_COMPILATION_CACHE_DIR`` JAX has already read it
and no directory is set in code; otherwise the cache lives at the fixed
``<checkout>/.jax_cache``.  The directory is part of the cache key, so
it is never derived from a temporary name, a pid or the clock.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Persist every executable: the scheduler's kernels are many and
    # individually quick to compile, and a cold daemon pays all of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
