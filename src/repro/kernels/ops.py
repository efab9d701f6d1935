"""Public wrappers for the Pallas kernels.

Interpret mode (Python emulation of the kernel body) is used only on the
CPU backend (`kernels.fingerprint.default_interpret`); on the chip every
kernel compiles. Block shapes stay identical either way, so VMEM footprints
claimed by the BlockSpecs are what a TPU sees.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.fingerprint import fingerprint_pallas
from repro.kernels.flash_attention import flash_attention_pallas


def fingerprint(x, block_rows: Optional[int] = None) -> jnp.ndarray:
    """Fused fingerprint of one tensor -> (4,) uint32."""
    return fingerprint_pallas(x, block_rows=block_rows)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128) -> jnp.ndarray:
    """Flash attention in model layout. q: (B,S,H,hd); k/v: (B,S,KV,hd).

    Returns (B,S,H,hd)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_pallas(qt, kt, vt, causal=causal, window=window,
                                 block_q=block_q, block_k=block_k)
    return out.transpose(0, 2, 1, 3)
