"""Where JAX keeps its persistent compilation cache.

Source of truth: the one place the program chooses the cache directory.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here touches the setting. Otherwise the cache lives in ``.jax_cache`` at the
root of the checkout (git-ignored). The path is fixed on purpose: a cache is
only found again at the path it was written to, so a directory named after a
temporary name, a pid or the time would never hit.

Entry points call ``enable_compile_cache()`` from their ``main()``, before
they compile anything; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
