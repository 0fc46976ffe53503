"""JAX's persistent compilation cache for the processes that hold a chip.

A process that serves from the chip (``rpc.main``, ``chip_smoke.py``)
calls ``enable()`` before its first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and nothing
is set here. Otherwise the cache lives at a fixed directory inside the
checkout: the path is part of what a later run must find again, so it is
never built from a temporary name, a pid or a time. A process pinned to
the CPU (``jax_platforms`` = "cpu", as every test is) keeps no cache.
"""

from __future__ import annotations

import os
from typing import Optional

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable() -> Optional[str]:
    """Turn the persistent compilation cache on; returns its directory,
    or None for a process pinned to the CPU."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    if jax.config.jax_platforms == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
