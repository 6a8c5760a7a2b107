"""JAX's persistent compilation cache at a path that stays put.

Entry points that compile for the device (``chip_smoke.py``, the hammer's
``main()``) call :func:`use_compile_cache` once at start-up; the library
never does it at import.  A cache key includes the cache's path, so the
directory must not move between runs: no temporary directory, pid or
timestamp.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

#: ``.jax_cache/`` at the root of the checkout (``src/repro/`` is two below)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX reads it and no
    other directory is set; otherwise the cache lives in
    :data:`CHECKOUT_CACHE_DIR`.  Every compile is kept, however short (the
    codec kernels compile in under JAX's default one-second floor), unless
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
