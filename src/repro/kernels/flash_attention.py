"""Pallas TPU kernel: tiled online-softmax (flash) attention, forward.

Grid (B, H, nQ, nK) with the KV axis innermost — TPU grids execute
sequentially per core, so the (m, l, acc) running state lives in VMEM
scratch and is carried across the nK steps of one (b, h, iq) tile.

Tiles: q (1,1,bq,hd), k/v (1,1,bk,hd) with bq=bk=128 in production
(MXU-aligned: the two matmuls are (bq,hd)x(hd,bk) and (bq,bk)x(bk,hd),
all dims multiples of 128 when hd in {64,128,256} — hd=64 still fills half
the MXU and is the hardware minimum lane packing). f32 accumulation.

GQA: the kernel receives per-q-head indices and maps kv loads through
h // group_size in the BlockSpec index map — no kv replication in HBM.

Masks: causal and/or sliding window, applied from absolute tile offsets.
Fully-masked tiles still run (grid has no control flow) — skipping them via
a cost model is a documented TPU-side optimization; correctness is
mask-exact. Validated in interpret mode against ref.py::mha_ref.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(scale, causal, window, bq, bk, seq_k,
                  q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jnp.einsum("qd,kd->qk", q, k,
                   preferred_element_type=jnp.float32) * scale   # (bq, bk)

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < seq_k
    if causal:
        mask = mask & (qpos >= kpos)
    if window:
        mask = mask & (qpos - kpos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                            # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + jnp.einsum("qk,kd->qd", p, v,
                                 preferred_element_type=jnp.float32))
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _final():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: Optional[bool] = None):
    """q: (B, H, Sq, hd); k/v: (B, KV, Sk, hd) — GQA when KV < H.

    Returns (B, H, Sq, hd) in q.dtype. `interpret=None` follows the
    backend (interpret mode only on the CPU)."""
    if interpret is None:
        from repro.kernels.fingerprint import default_interpret
        interpret = default_interpret()
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    assert H % KV == 0, (H, KV)
    group = H // KV

    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    # pad sequences to tile multiples (masked out via seq_k / qpos bounds;
    # padded q rows produce garbage that the wrapper slices away)
    pq = (-Sq) % bq
    pk = (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nQ = q.shape[2] // bq
    nK = k.shape[2] // bk

    kern = functools.partial(_flash_kernel, 1.0 / math.sqrt(hd), causal,
                             window, bq, bk, Sk)
    out = pl.pallas_call(
        kern,
        grid=(B, H, nQ, nK),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, q.shape[2], hd), q.dtype),
        scratch_shapes=[
            _vmem((bq, hd), jnp.float32),
            _vmem((bq,), jnp.float32),
            _vmem((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq, :]


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
