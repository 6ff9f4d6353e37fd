"""Placement of JAX's persistent compilation cache — one rule for every
entry point (``python -m xgboost_tpu``, ``python -m xgboost_tpu.serving``,
``chip_smoke.py``).

The cache directory is part of how a run is deployed, so it is placed
from OUTSIDE the program: where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and this module sets no directory in code.
Otherwise the cache sits at ``<checkout>/.jitcache`` — a FIXED path
(the directory is part of the cache key's environment: one that moves
between runs never hits), listed in ``.gitignore``; it is a cache, never
an input.  ``XGBTPU_NO_JITCACHE`` (any value) leaves JAX's own settings
alone entirely.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jitcache")


def configure_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on and return the directory
    in use (None = this module left it off).  Call before the first
    compilation; safe to call more than once.

    Every compilation is kept, however short or small: a resumed gang,
    a restarted server and a second chip run all replay the same few
    dozen programs, and a skipped entry is a recompile."""
    if os.environ.get("XGBTPU_NO_JITCACHE"):
        return None
    import jax
    cache_dir = os.environ.get(CACHE_DIR_ENV)
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        try:
            os.makedirs(cache_dir, exist_ok=True)
        except OSError as e:
            # read-only install: run uncached rather than not at all
            print(f"[compile-cache] {cache_dir}: {e}; persistent "
                  "compilation cache off", file=sys.stderr)
            return None
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
