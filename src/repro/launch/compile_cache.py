"""JAX's persistent compilation cache for the launchers and the chip smoke
run.

A compiled program is keyed by, among other things, the cache directory,
so the directory must not move between runs: where the environment names
one (`JAX_COMPILATION_CACHE_DIR`, which JAX reads itself) it is left alone,
and otherwise the cache lives at a fixed path inside the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile
    and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
