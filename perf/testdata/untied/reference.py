"""Plain float32 decoder with no QKV bias and an untied output head: the
reference of the harness tests' second architecture.

The block is Qwen2's (`perf/reference/qwen2.py`: RMSNorm, grouped-query
attention with rotate-half RoPE, SwiGLU MLP) with two departures, which
are what this architecture is for: Q, K and V have no bias, and the logits
come from `lm_head` (vocab, hidden), a matrix of their own, not from the
token embedding. `quant="fp8"` rounds the operands of every matrix product
as the qwen2 reference does.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from perf.reference.qwen2 import HIGHEST, fake_quant, rms_norm, rope

KEYS = ("num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "rms_norm_eps", "rope_theta")


def _einsum(spec, a, b, quant):
    return jnp.einsum(spec, fake_quant(a, quant), fake_quant(b, quant),
                      precision=HIGHEST)


def _layer(cfg, lw, x, quant):
    S = x.shape[0]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    h = rms_norm(x, lw["input_norm"], cfg["rms_norm_eps"])
    q = rope(_einsum("sd,de->se", h, lw["q_w"], quant).reshape(S, H, hd),
             cfg["rope_theta"])
    k = rope(_einsum("sd,de->se", h, lw["k_w"], quant).reshape(S, KV, hd),
             cfg["rope_theta"])
    v = _einsum("sd,de->se", h, lw["v_w"], quant).reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    scores = _einsum("qhd,khd->hqk", q, k, quant) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = _einsum("hqk,khd->qhd", p, v, quant).reshape(S, H * hd)
    x = x + _einsum("se,ed->sd", o, lw["o_w"], quant)
    h = rms_norm(x, lw["post_norm"], cfg["rms_norm_eps"])
    g = _einsum("sd,df->sf", h, lw["gate_w"], quant)
    u = _einsum("sd,df->sf", h, lw["up_w"], quant)
    return x + _einsum("sf,fd->sd", jax.nn.silu(g) * u, lw["down_w"], quant)


@functools.partial(jax.jit, static_argnums=(1, 3))
def _hidden(w, cfg_items, tokens, quant):
    cfg = dict(cfg_items)
    x = fake_quant(w["embed"], quant)[tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(cfg, jax.tree.map(lambda a: a[i], w["layers"]), x, quant)
    return rms_norm(x, w["final_norm"], cfg["rms_norm_eps"])


def hidden(w: Dict[str, Any], cfg: Dict[str, Any], tokens,
           quant: Optional[str] = None):
    """Final-normed hidden states (S, hidden) of one token sequence."""
    items = tuple((k, cfg[k]) for k in KEYS)
    return _hidden(w, items, jnp.asarray(tokens, jnp.int32), quant)


def logits(w: Dict[str, Any], h, quant: Optional[str] = None):
    """Rows of logits from final hidden states h: (rows, hidden)."""
    return _einsum("sd,vd->sv", h, w["lm_head"], quant)
