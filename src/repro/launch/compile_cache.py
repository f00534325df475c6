"""Where JAX's persistent compilation cache lives — one decision for every
entry point (``chip_smoke.py`` and the launchers).

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no other path is
  set here.
* otherwise: ``<checkout>/.jax_cache`` — a fixed path (the cache key
  includes it, so a moving directory would never hit), listed in
  ``.gitignore``.

Tests never call this: their compiles stay uncached.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
