"""Persistent XLA compilation cache for the programs a user runs.

The entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``, ``benchmarks/serve_throughput.py``) call
:func:`enable_compile_cache` before their first compile. Library code and
tests never do: importing the package changes no jax setting.

The cache directory is part of what makes an entry hit, so it is fixed:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (jax reads
that variable itself, and nothing else is set), otherwise ``.jax_cache``
at the root of the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
