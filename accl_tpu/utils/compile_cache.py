"""Where JAX keeps its persistent compilation cache.

Called by the entry scripts (chip_smoke.py, bench.py, examples/) before
their first compile, never on import of the library: a library that turns
on a cache for whoever imports it writes files its users did not ask for.
"""

from __future__ import annotations

import os
import pathlib

# a fixed path: the directory is part of what the cache is found by, so a
# name made from a temp dir, a pid or the time would never hit
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and nothing
    is set here. Otherwise the cache goes to `<repo>/.jax_cache`."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
