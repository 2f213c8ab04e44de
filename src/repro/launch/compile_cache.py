"""JAX's persistent compilation cache, placed once at start-up.

Entry points (``launch/serve.py``, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`init_compile_cache` before they compile
anything; importing this module changes nothing.  Where the
``JAX_COMPILATION_CACHE_DIR`` environment variable is set, JAX reads it
itself and this module sets no directory; otherwise the cache lives in
``.jax_cache/`` at the repository root, a fixed path so that a restart
finds its entries again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Every
    program is cached, however quickly it compiled: the small ingest
    and RS programs cost a chip process seconds each on a cold start."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
