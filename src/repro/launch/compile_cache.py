"""Where the entry points keep JAX's persistent compilation cache.

Called by the entry points (launch/serve.py, launch/train.py,
benchmarks/run.py, chip_smoke.py), never when a library module is
imported. The cache directory is part of what a later process needs to
hit the cache, so it is a fixed path, never one built from a temp name,
a pid or the time.
"""

from __future__ import annotations

import os
import pathlib

CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]


def place_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache is ``.jax_cache/`` at the
    checkout root (listed in .gitignore)."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
