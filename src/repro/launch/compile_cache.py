"""Persistent XLA compile cache shared by every entry point.

Each entry point (``serve``, ``train``, ``chip_smoke.py``) calls
:func:`enable_compile_cache` before its first compile, so a second run of the
28-layer decode scan finds its programs on disk instead of compiling again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left alone: JAX reads it itself.
    Otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed path, since
    a directory that moves between runs never hits.

    Source locations are left out of lowered programs.  The cache key strips them
    from the XLA module, but not from a Pallas kernel's serialized Mosaic body,
    which would carry the kernel's absolute path: every program that calls the
    kernel would then miss in a checkout at another path.
    """
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
