"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points that compile for the chip (``chip_smoke.py``,
``benchmarks/run_all.py``) call :func:`use_compile_cache` before their
first compile.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that
directory itself and no other is set here; otherwise the cache lives at
``<checkout>/.jax_cache``.  The path is part of each entry's key, so it
never depends on a temp dir, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on for every compile, however short,
    and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
