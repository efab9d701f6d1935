"""Pallas TPU kernel: fused state fingerprint (hash + sum + absmax).

SEDAR's hot spot is the comparison/validation pass over every byte of
gradient/parameter state (DESIGN.md §5). This kernel computes, in a single
HBM pass over one tensor's 32-bit words u_i at GLOBAL positions
i = offset + row-major index:

    h1 = sum_i ((u_i XOR (i*C1)) * C2)        mod 2^32
    h2 = sum_i (t XOR (t >> 15)), t=(u_i+i)*C3
    s  = sum(x)       (f32)
    a  = max(|x|)     (f32)

The hash words are identical bit-for-bit to the pure-jnp oracles
(`repro.core.fingerprint.tensor_fingerprint` at offset 0, and the per-leaf
partials of `pytree_fingerprint_fused` at the leaf's global offset). Both
reductions are modular adds, so per-tensor partials at global offsets sum
to the hash of the logically packed state — the whole-state fingerprint
hashes every leaf IN PLACE, never through a concatenated copy.

Layout: a tensor whose last dim is a multiple of 128 (and whose
second-to-last dim is a multiple of 8 when it has more than two dims) is
viewed as (rows, cols) without moving a byte — merging leading dims keeps
the chip's (8, 128) tiled layout. Anything else (1-D buffers, head_dim-64
leaves, short tensors) is flattened and padded to (rows, 128), a copy of
that one leaf. The view is streamed in (block_rows, block_cols) tiles; an
inner loop walks each tile one (8, 128) vreg at a time and folds it into
four (8, 128) accumulator tiles that stay resident in the output across the
sequential grid. The lane-dense accumulators are reduced to scalars after
the call, so nothing stores a scalar to VMEM and the out BlockSpec keeps
the (8, 128) tiling under `jax.vmap` (the replica axis of the fused
executor's validation becomes a leading grid axis). Partial edge tiles and
padding words are masked by position. All in-kernel arithmetic is int32
with logical shifts, which is bit-identical to the u32 definition
(add/mul/xor wrap mod 2^32). Arithmetic intensity is O(1) op/byte: the
kernel is memory-bound by design and its roofline cost is one read of the
state.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

C1 = np.uint32(2654435761)
C2 = np.uint32(2246822519)
C3 = np.uint32(3266489917)

LANES = 128
SUBLANES = 8
BLOCK_WORDS = 1 << 18         # words per input tile: 1 MiB of VMEM
MAX_BLOCK_COLS = 2048


def default_interpret() -> bool:
    """Pallas interpret mode only on the CPU backend (the test container);
    on the chip every kernel compiles."""
    return jax.default_backend() == "cpu"


def _i32(v) -> np.int32:
    """u32 bit pattern of a Python int as an int32 constant."""
    return np.uint32(int(v) % (1 << 32)).view(np.int32)


def _fingerprint_kernel(n_valid, n_rows, n_cols, offset, u_ref, h1_ref,
                        h2_ref, s_ref, a_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    block_rows, block_cols = u_ref.shape

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init():
        h1_ref[...] = jnp.zeros_like(h1_ref)
        h2_ref[...] = jnp.zeros_like(h2_ref)
        s_ref[...] = jnp.zeros_like(s_ref)
        a_ref[...] = jnp.zeros_like(a_ref)

    sub = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
    c1, c2, c3 = _i32(C1), _i32(C2), _i32(C3)
    zero = jnp.int32(0)

    def body(k, carry):
        h1, h2, s, a = carry
        r = pl.multiple_of(k * SUBLANES, SUBLANES)
        row = i * block_rows + r + sub                     # view row
        for c in range(0, block_cols, LANES):
            u = u_ref[pl.ds(r, SUBLANES), pl.ds(c, LANES)]  # (8, 128)
            if u.dtype != jnp.int32:
                u = jax.lax.bitcast_convert_type(u, jnp.int32)
            pos = row * n_cols + (j * block_cols + c) + lane
            valid = jnp.logical_and(row < n_rows, pos < n_valid)
            idx = pos + _i32(offset)                       # global position
            t1 = (u ^ (idx * c1)) * c2
            t2 = (u + idx) * c3
            t2 = t2 ^ jax.lax.shift_right_logical(t2, jnp.int32(15))
            xf = jax.lax.bitcast_convert_type(u, jnp.float32)
            h1 = h1 + jnp.where(valid, t1, zero)
            h2 = h2 + jnp.where(valid, t2, zero)
            s = s + jnp.where(valid, xf, 0.0)
            a = jnp.maximum(a, jnp.where(valid, jnp.abs(xf), 0.0))
        return h1, h2, s, a

    carry = (h1_ref[...], h2_ref[...], s_ref[...], a_ref[...])
    h1, h2, s, a = jax.lax.fori_loop(0, block_rows // SUBLANES, body, carry)
    h1_ref[...] = h1
    h2_ref[...] = h2
    s_ref[...] = s
    a_ref[...] = a


def _lane_view(u) -> jnp.ndarray:
    """(rows, cols) view of a 32-bit word tensor, cols a multiple of 128 and
    rows >= 8. In place where the tiled layout allows it, else a padded flat
    copy of this one tensor (the padding words are masked in the kernel)."""
    shape, n = u.shape, u.size
    if (len(shape) >= 2 and shape[-1] % LANES == 0
            and (len(shape) == 2 or shape[-2] % SUBLANES == 0)
            and n // shape[-1] >= SUBLANES):
        return u.reshape(n // shape[-1], shape[-1])
    rows = max(-(-n // LANES), SUBLANES)
    flat = u.reshape(-1)
    if rows * LANES > n:
        flat = jnp.pad(flat, (0, rows * LANES - n))
    return flat.reshape(rows, LANES)


def _block_cols(cols: int) -> int:
    """Widest multiple of 128 that divides `cols` and fits MAX_BLOCK_COLS."""
    m = cols // LANES
    return LANES * max(d for d in range(1, MAX_BLOCK_COLS // LANES + 1)
                       if m % d == 0)


def fingerprint_partials(x, offset: int = 0,
                         block_rows: Optional[int] = None,
                         interpret: Optional[bool] = None
                         ) -> Tuple[jnp.ndarray, ...]:
    """Fingerprint terms of one tensor (any shape and dtype; its words as
    `core.fingerprint.words_u32` defines them, in row-major order) whose
    first word sits at global position `offset` -> (h1 u32, h2 u32, sum
    f32, absmax f32) scalars. Partials of consecutive tensors combine by
    modular add (h1, h2), add (sum) and max (absmax). `interpret=None`
    follows the backend.

    32-bit tensors enter the kernel as they are and are reinterpreted
    inside it: a bitcast ahead of the custom call would be materialized as
    a copy of the tensor."""
    if interpret is None:
        interpret = default_interpret()
    u = jnp.asarray(x)
    if u.dtype not in (jnp.float32, jnp.int32, jnp.uint32):
        from repro.core.fingerprint import words_u32
        u = words_u32(u)
    n = int(u.size)
    if n >= 1 << 31:
        raise ValueError(f"fingerprint buffer of {n} words exceeds int32 "
                         "positions; split it into leaves")
    view = _lane_view(u)
    rows, cols = view.shape
    bc = _block_cols(cols)
    br = block_rows or max(SUBLANES, BLOCK_WORDS // bc // SUBLANES * SUBLANES)
    br = min(-(-int(br) // SUBLANES) * SUBLANES,
             -(-rows // SUBLANES) * SUBLANES)
    acc = jax.ShapeDtypeStruct((SUBLANES, LANES), jnp.int32)
    accf = jax.ShapeDtypeStruct((SUBLANES, LANES), jnp.float32)
    h1, h2, s, a = pl.pallas_call(
        functools.partial(_fingerprint_kernel, n, rows, cols, offset),
        grid=(pl.cdiv(rows, br), cols // bc),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((SUBLANES, LANES), lambda i, j: (0, 0))] * 4,
        out_shape=[acc, acc, accf, accf],
        interpret=interpret,
        name="sedar_fingerprint",
    )(view)
    return (jnp.sum(jax.lax.bitcast_convert_type(h1, jnp.uint32),
                    dtype=jnp.uint32),
            jnp.sum(jax.lax.bitcast_convert_type(h2, jnp.uint32),
                    dtype=jnp.uint32),
            jnp.sum(s), jnp.max(a))


def fingerprint_pallas(x, block_rows: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """-> (4,) uint32 [h1, h2, bits(sum), bits(absmax)], bit-identical to
    fingerprint_ref on the hash words. Accepts any floating dtype (exact
    upcast to f32 first, matching the oracle) or an already-packed uint32
    buffer (hashed as-is, no bitcast)."""
    h1, h2, s, a = fingerprint_partials(x, 0, block_rows, interpret)
    return jnp.stack([h1, h2, jax.lax.bitcast_convert_type(s, jnp.uint32),
                      jax.lax.bitcast_convert_type(a, jnp.uint32)])
