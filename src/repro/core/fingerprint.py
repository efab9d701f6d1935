"""State fingerprinting — SEDAR's comparison primitive.

The paper compares full message buffers between replicated threads (cheap in
a shared L2). On TPU the replicas are pods, so we compress every tensor into
a 128-bit fingerprint + 2 diagnostic stats in ONE streaming pass and compare
only fingerprints across the replica axis (a few hundred bytes over ICI/DCN).

Fingerprint of a tensor (after exact upcast to f32 and bitcast to u32):
    h1 = sum_i ((x_i XOR (i * C1)) * C2)       mod 2^32  (order-sensitive sum)
    h2 = sum_i (t XOR (t >> 15)), t = (x_i+i)*C3         (independent mix)
    s  = sum(x)  (f32)                                   (diagnostic)
    a  = max(|x|) (f32)                                  (diagnostic)

(Both hashes reduce with modular ADD — XLA lowers add-reductions everywhere
incl. SPMD partitions; xor-fold reductions are rejected by some backends.)

Both h1 and h2 are associative/commutative reductions over position-mixed
words, so they vectorize on the VPU, tile cleanly in VMEM (see
kernels/fingerprint.py for the Pallas version) and are bitwise deterministic.
A single flipped bit anywhere changes h1 (and almost surely h2).

`pytree_fingerprint` returns a (n_leaves, 4) uint32 array (stats bitcast), so
replica comparison is a single small array equality.

Two granularities (DESIGN.md §5):
  * per-leaf  -- `pytree_fingerprint` -> (n_leaves, 4). One reduction per
    leaf; keeps leaf-level localization for `mismatch_report`.
  * fused     -- `pytree_fingerprint_fused` -> (4,). The hash of all leaves
    LOGICALLY packed (bit-exactly, via `_to_u32`) into one flat u32 buffer:
    each leaf is hashed in place at its global word offset and the partials
    are summed, so the comparison hot path gets one fingerprint per state
    without a packed copy. The fused hash is NOT comparable to per-leaf
    hashes (different index stream); both replicas must use the same
    granularity.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

C1 = np.uint32(2654435761)   # Knuth multiplicative
C2 = np.uint32(2246822519)   # xxhash prime
C3 = np.uint32(3266489917)   # xxhash prime


def words_u32(x) -> jnp.ndarray:
    """Exact reinterpretation of any dtype as u32 words, shape kept (so the
    chip's kernel can read a leaf in its own tiled layout)."""
    x = jnp.asarray(x)
    if x.dtype in (jnp.float64, jnp.int64):  # CPU tests may use 64-bit
        x = x.astype(jnp.float32) if jnp.issubdtype(x.dtype, jnp.floating) \
            else x.astype(jnp.int32)
    if x.dtype in (jnp.bfloat16, jnp.float16):
        x = x.astype(jnp.float32)            # exact upcast
    if x.dtype == jnp.float32:
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    elif x.dtype in (jnp.int32, jnp.uint32):
        u = x.astype(jnp.uint32)
    elif x.dtype == jnp.bool_:
        u = x.astype(jnp.uint32)
    elif x.dtype in (jnp.int8, jnp.uint8, jnp.int16, jnp.uint16):
        u = x.astype(jnp.uint32)
    else:
        raise TypeError(f"unsupported dtype {x.dtype}")
    return u


def _to_u32(x) -> jnp.ndarray:
    """Exact reinterpretation of any dtype as a flat u32 vector."""
    return words_u32(x).reshape(-1)


def tensor_fingerprint(x) -> jnp.ndarray:
    """-> (4,) uint32: [h1, h2, bits(sum), bits(absmax)]."""
    u = _to_u32(x)
    n = u.shape[0]
    idx = jax.lax.iota(jnp.uint32, n)
    h1 = jnp.sum((u ^ (idx * C1)) * C2, dtype=jnp.uint32)
    t2 = (u + idx) * C3
    h2 = jnp.sum(t2 ^ (t2 >> jnp.uint32(15)), dtype=jnp.uint32)
    xf = jnp.asarray(x)
    if jnp.issubdtype(xf.dtype, jnp.floating):
        xf32 = xf.astype(jnp.float32)
        s = jnp.sum(xf32)
        a = jnp.max(jnp.abs(xf32)) if xf.size else jnp.float32(0)
    else:
        s = jnp.float32(0)
        a = jnp.float32(0)
    sb = jax.lax.bitcast_convert_type(s.astype(jnp.float32), jnp.uint32)
    ab = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    return jnp.stack([h1, h2, sb, ab])


def pytree_fingerprint(tree, use_pallas: bool = False) -> jnp.ndarray:
    """-> (n_leaves, 4) uint32, leaf order = tree_flatten order."""
    leaves = jax.tree.leaves(tree)
    if use_pallas:
        from repro.kernels.ops import fingerprint as fp_kernel
        fps = [fp_kernel(l) for l in leaves]
    else:
        fps = [tensor_fingerprint(l) for l in leaves]
    return jnp.stack(fps) if fps else jnp.zeros((0, 4), jnp.uint32)


def pack_tree_u32(tree) -> jnp.ndarray:
    """Bit-exact packing of every leaf into one flat u32 buffer
    (tree_flatten order). The packing is a reinterpretation, not a value
    conversion, so any single corrupted bit in any leaf is a corrupted bit
    in the packed buffer."""
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.zeros((0,), jnp.uint32)
    return jnp.concatenate([_to_u32(l) for l in leaves])


def packed_fingerprint(u: jnp.ndarray) -> jnp.ndarray:
    """Fingerprint of an already-packed u32 buffer -> (4,) uint32.

    Same mixing as `tensor_fingerprint`, with the kernel's diagnostic
    convention: sum/absmax are computed over the f32 REINTERPRETATION of the
    packed words (matches kernels/fingerprint.py bit-for-bit on the hash
    words; the float stats are diagnostics only).

    Non-u32 input is bit-reinterpreted via `_to_u32` (never value-cast —
    a value cast would truncate every float in (-1, 1) to 0 and make the
    fingerprint blind to corruption)."""
    u = jnp.asarray(u)
    if u.dtype != jnp.uint32:
        u = _to_u32(u)
    return _combine([_words_partials(u, 0)] if u.size else [])


def _row_major_index(shape) -> jnp.ndarray:
    """u32 row-major position of every element of an array of `shape`."""
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for d in reversed(range(len(shape))):
        idx = idx + (jax.lax.broadcasted_iota(jnp.uint32, shape, d)
                     * jnp.uint32(stride % (1 << 32)))
        stride *= shape[d]
    return idx


def _words_partials(x, offset: int, lo: int = 0, hi: Optional[int] = None):
    """jnp fingerprint terms of words [lo, hi) of one tensor (row-major
    order), word i at global position offset + i -> (h1, h2, sum, absmax).
    The tensor is read in its own shape, never through a flattened or
    sliced copy."""
    u = words_u32(x)
    i = _row_major_index(u.shape)
    idx = jnp.uint32(offset % (1 << 32)) + i
    t1 = (u ^ (idx * C1)) * C2
    t2 = (u + idx) * C3
    t2 = t2 ^ (t2 >> jnp.uint32(15))
    xf = jax.lax.bitcast_convert_type(u, jnp.float32)
    if lo > 0 or (hi is not None and hi < u.size):
        inside = (i >= jnp.uint32(lo)) & (i < jnp.uint32(u.size if hi is None
                                                         else hi))
        t1 = jnp.where(inside, t1, jnp.uint32(0))
        t2 = jnp.where(inside, t2, jnp.uint32(0))
        xf = jnp.where(inside, xf, 0.0)
    return (jnp.sum(t1, dtype=jnp.uint32), jnp.sum(t2, dtype=jnp.uint32),
            jnp.sum(xf), jnp.max(jnp.abs(xf)))


def _combine(partials) -> jnp.ndarray:
    """Fold (h1, h2, sum, absmax) partials into one (4,) fingerprint."""
    if not partials:
        return jnp.zeros((4,), jnp.uint32)
    h1s, h2s, ss, as_ = zip(*partials)
    return jnp.stack([
        jnp.sum(jnp.stack(h1s), dtype=jnp.uint32),
        jnp.sum(jnp.stack(h2s), dtype=jnp.uint32),
        jax.lax.bitcast_convert_type(jnp.sum(jnp.stack(ss)), jnp.uint32),
        jax.lax.bitcast_convert_type(jnp.max(jnp.stack(as_)), jnp.uint32)])


def pytree_fingerprint_fused(tree, use_pallas: Optional[bool] = None
                             ) -> jnp.ndarray:
    """Whole-state fingerprint -> (4,) uint32: ONE fingerprint over the
    logically-packed state instead of one per leaf.

    Every leaf is hashed IN PLACE with its GLOBAL word offset folded into
    the index stream, and the per-leaf partials combine with one final
    add/max. Modular-add reductions are associative/commutative, so the
    partials sum to exactly the hash of `pack_tree_u32(tree)` — without
    materializing the concatenation (an extra full write+read pass and a
    second copy of the state in device memory). Two value-identical
    lowerings of the per-leaf partials (hash words compare equal — verified
    by tests):
      * Pallas (the chip): `kernels.fingerprint.fingerprint_partials`, one
        streaming kernel per leaf.
      * jnp (the CPU backend): XLA reductions.

    `use_pallas=None` selects Pallas everywhere but the CPU backend."""
    if use_pallas is None:
        from repro.kernels.fingerprint import default_interpret
        use_pallas = not default_interpret()
    if use_pallas:
        from repro.kernels.fingerprint import fingerprint_partials as partials
    else:
        partials = _words_partials

    parts = []
    offset = 0
    for l in jax.tree.leaves(tree):
        n = int(np.size(l))
        if n:
            parts.append(partials(l, offset))
        offset += n
    return _combine(parts)


def pytree_fingerprint_lanes(tree, n_lanes: int) -> jnp.ndarray:
    """Per-shard fingerprint lanes -> (n_lanes, 4) uint32 (DESIGN.md §16).

    The packed state is split into `n_lanes` equal contiguous chunks
    (zero-padded tail) and each chunk is hashed independently (each leaf in
    place, never through a packed copy of the state), so a replica
    divergence localizes to the lane covering the corrupted words instead
    of collapsing into one whole-state bit. Lane i covers packed u32 words
    [i*W, (i+1)*W), W = ceil(N/n_lanes); callers align n_lanes with shard
    ownership (lane index -> data shard -> host, see
    runtime/cluster.lanes_to_hosts). NOT comparable with the fused or
    per-leaf granularities (different index streams)."""
    L = max(int(n_lanes), 1)
    leaves = [l for l in jax.tree.leaves(tree) if np.size(l)]
    total = sum(int(np.size(l)) for l in leaves)
    if total == 0:
        return jnp.zeros((L, 4), jnp.uint32)
    width = -(-total // L)
    if L * width > total:   # the zero-padded tail is hashed like a leaf
        leaves.append(jnp.zeros((L * width - total,), jnp.uint32))
    # each leaf is hashed in place, once per lane it overlaps, at its
    # lane-local word positions
    parts = [[] for _ in range(L)]
    offset = 0
    for l in leaves:
        n = int(np.size(l))
        for lane in range(offset // width, (offset + n - 1) // width + 1):
            start = lane * width
            parts[lane].append(_words_partials(
                l, offset - start, lo=max(start - offset, 0),
                hi=min(start + width - offset, n)))
        offset += n
    return jnp.stack([_combine(p) for p in parts])


def lane_of_leaf_index(tree, leaf_idx: int, flat_idx: int, n_lanes: int
                       ) -> int:
    """Host-side: which fingerprint lane covers element `flat_idx` of leaf
    `leaf_idx` (tree_flatten order) under `pytree_fingerprint_lanes`.
    Assumes 32-bit leaves (one packed word per element), which holds for
    every training state here after `_to_u32`'s 64->32 narrowing."""
    leaves = jax.tree.leaves(tree)
    off = sum(int(np.size(l)) for l in leaves[:leaf_idx]) + int(flat_idx)
    total = sum(int(np.size(l)) for l in leaves)
    L = max(int(n_lanes), 1)
    width = -(-total // L)
    return off // width


def fingerprints_equal(fp_a, fp_b) -> jnp.ndarray:
    """Exact equality on the hash words (cols 0..1); stats are diagnostics."""
    return jnp.all(fp_a[..., :2] == fp_b[..., :2])


def mismatch_report(tree, fp_a, fp_b):
    """Host-side: list of (leaf_path, fp_a_row, fp_b_row) that differ."""
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    a = np.asarray(fp_a)
    b = np.asarray(fp_b)
    out = []
    for i, path in enumerate(paths):
        if not np.array_equal(a[i, :2], b[i, :2]):
            out.append({
                "leaf": path,
                "h_a": [int(a[i, 0]), int(a[i, 1])],
                "h_b": [int(b[i, 0]), int(b[i, 1])],
                "sum_a": float(np.frombuffer(a[i, 2].tobytes(), np.float32)[0]),
                "sum_b": float(np.frombuffer(b[i, 2].tobytes(), np.float32)[0]),
            })
    return out
