"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (`chip_smoke.py`, `repro.launch.serve`, the benchmarks) call
:func:`enable_compile_cache` first thing in ``main()``; importing this
module changes nothing. A later process finds a compiled program again
only if it looks in the same directory, so the path is fixed: never a
temporary name, a process id or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (listed in .gitignore).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other path is set here. Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`. Every program is cached, not only those
    that took JAX's default minimum of a second to compile.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
