"""Persistent compilation cache for the entry points.

Called from each ``main()`` (never at import): `chip_smoke.py`,
``repro.launch.serve`` and ``repro.launch.train``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed path inside the checkout (gitignored): the cache key includes the
# directory, so a path that moved between runs would never hit
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX reads it; only
    when it is unset does the cache go to the checkout's ``.jax_cache/``."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return jax.config.jax_compilation_cache_dir
