"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points call `enable_compile_cache()` first thing in `main`, never at
import. `JAX_COMPILATION_CACHE_DIR`, when set, wins and nothing is set in
code (JAX reads the variable itself). Otherwise the cache lives at the fixed
path `<checkout>/.jax_cache`: the path is part of each entry's key, so a
directory that moved between runs (a temp name, a pid) would never hit.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent cache; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
