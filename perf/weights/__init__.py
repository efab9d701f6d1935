"""Weights made from a seed: one module per architecture, named by a
configuration's `weights` key (see `perf/run.py`)."""
from __future__ import annotations

import jax


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed of up to 64 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
