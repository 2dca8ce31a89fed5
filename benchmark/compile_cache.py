"""JAX's persistent compilation cache for benchmark runs.

The benchmark's own copy of accl_tpu/utils/compile_cache.py, so that a
change to the program cannot move where the yardstick caches. It also
sets the minimum compile time to 0: JAX's default of 1 s leaves small
programs out of the cache, and they would compile again in every run.
"""

from __future__ import annotations

import os
import pathlib

# a fixed path inside the checkout: the directory is part of what the
# cache is found by, so a name made from a temp dir, a pid or the time
# would never hit
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on before the first compile; return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and the
    directory is left as it is. Otherwise the cache goes to
    `<checkout>/.jax_cache`."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir
