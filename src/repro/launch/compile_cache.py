"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` before they compile anything.
``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and this
module sets nothing.  Otherwise the cache goes to ``.jax_cache`` at the root
of the checkout (listed in ``.gitignore``).  The path is fixed on purpose:
a directory named after a temporary file, a process id or the time would
differ on every run, and no run would find what an earlier one wrote.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
