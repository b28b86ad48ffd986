"""JAX's persistent compilation cache, switched on in one place.

Every process that compiles for the chip (``chip_smoke.py``, ``bench.py``'s
row children) calls :func:`enable_compile_cache` before its first compile.
The cache directory is part of the cache key, so it must not move between
runs: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
directory is set here; otherwise it is ``<checkout>/.jax_cache``
(git-ignored) — never a temp name, a pid or a time.

A Pallas kernel rides in its program as an opaque string (the serialized
Mosaic module), and the call stacks JAX writes into that module's locations
are therefore part of the key: they vary with the checkout's path, the call
site, and with what the process lowered before (a run that raced the flash
blocks and a run that read the record produced different programs, and the
first chip runs recompiled the LM train step every time). The helper turns
those call stacks off, so the same kernel is the same program everywhere.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
#: the one place in the tree that names JAX's option
CONFIG_NAME = "jax_compilation_cache_dir"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its directory."""
    import jax

    jax.config.update("jax_traceback_in_locations_limit", 0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update(CONFIG_NAME, path)
    return path
