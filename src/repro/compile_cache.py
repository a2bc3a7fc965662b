"""Persistent XLA compilation cache for the command-line entry points.

The float64 control step takes tens of seconds to compile for a TPU, so the
scripts (``chip_smoke.py``, ``benchmarks/*``, ``examples/*``) keep compiled
programs on disk.  Importing :mod:`repro` never turns the cache on: library
callers and the tests keep JAX's own defaults.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

# one fixed path at the checkout root, so every run of the scripts finds the
# programs the previous one compiled
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` when it
    is set, otherwise at ``.jax_cache/`` in the checkout.  Returns the
    directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
