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

**The key holds the metadata** (``jax_compilation_cache_include_metadata_in_key``
on; JAX's default strips it). With the call stacks off a location is the
operation's name stack and nothing else (``jit(step)/jvp(embed)/gather``): no
path, no line. So the same tree still hits from any checkout, and two trees
that differ in their ``jax.named_scope``s alone, whose programs are the same
op for op, no longer share an entry. They must not: a profile reads the
scopes out of the executable (each device event's ``tf_op``), and with the
default key a traced run read the names of whichever tree had compiled that
program first (PERF.md section 6, PR 33; measured again at PR 37, section 3).

The helper also starts the process's compile account
(``obs.compiles.listen_to_compile_phases``: seconds by phase and the cache's
misses, in ``obs.get_registry()``), once, however often it is called.
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

    from ..obs.compiles import listen_to_compile_phases
    listen_to_compile_phases()
    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update(CONFIG_NAME, path)
    return path
