"""JAX's persistent compilation cache, switched on by entry points.

Processes that compile for the chip (the chip smoke, each device-owning
cluster worker, the ``launch/`` and ``benchmarks/`` entry points) call
:func:`enable_compile_cache` once, before their first compile. Library
imports never do: whether a process caches is its entry point's choice.
"""

from __future__ import annotations

import os
from pathlib import Path

# the checkout this package runs from (src/repro/core/ → root)
CHECKOUT = Path(__file__).resolve().parents[3]

# a fixed directory, never a temporary, per-process or per-run name: a
# cache that moves between runs never hits
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here. Every executable is cached, however
    quick its compile: the per-bucket ``pfor_jit`` programs compile in
    well under JAX's default one-second floor, and a fresh worker
    process would otherwise recompile each of them on every run."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
