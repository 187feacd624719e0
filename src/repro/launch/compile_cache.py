"""Persistent XLA compilation cache for the launchers.

Compiling the full-width serving steps takes tens of seconds to minutes per
shape; a persistent cache lets the next process on the same machine skip it.
The cache key includes its directory, so the directory must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when it is set, else ``.jax_cache`` at the
root of this checkout.  Called from each launcher's ``main()``, never at
import, so importing the library changes no global JAX state.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
