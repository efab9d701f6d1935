"""Plain float32 Qwen2 decoder: the yardstick that decides `correct`, and
the `reference` module that `configs/qwen2-0.5b.json` names.

Written from the published description of Qwen2 (arXiv:2407.10671 and
the `Qwen2ForCausalLM` config keys in `configs/*.json`) in straightforward
`jax.numpy`: one sequence at a time, full causal attention over the whole
sequence, no KV cache, no batching, no kernels, every matrix product at
`precision="highest"`. It imports nothing of the program under test and
takes its weights from `weights/qwen2.py`'s `reference_weights`. The
harness calls `hidden` and `logits` (`check.py`).

Per layer:  x += O(attn(rope(Q(n1(x))), rope(K(n1(x))), V(n1(x))))
            x += W_down(silu(W_gate(n2(x))) * W_up(n2(x)))
with RMSNorm n(x) = x / sqrt(mean(x^2) + eps) * w, Q/K/V with biases, no
bias on O, grouped-query attention (each key/value head serves
num_attention_heads / num_key_value_heads consecutive query heads), and
rotate-half RoPE with inv_freq = theta^(-2i/head_dim). The output head is
the transposed token embedding (tie_word_embeddings).

Departures from the published model, all of which leave the function of
a served token unchanged: dropout is left out (attention_dropout is 0);
the sliding window is left out (use_sliding_window is false); weights are
random (`weights.py`), not the released checkpoint.

`quant="fp8"` computes every matrix product (the four attention products,
the MLP, the head and the embedding lookup) from operands rounded to
float8 e4m3 with one absmax scale per tensor, accumulated in float32: the
lower-precision control of the comparison (see `check.py`).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0


def fake_quant(x, quant: Optional[str]):
    """Round `x` to the control's precision and back to float32."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _einsum(spec, a, b, quant):
    return jnp.einsum(spec, fake_quant(a, quant), fake_quant(b, quant),
                      precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (S, heads, head_dim), positions 0..S-1."""
    S, _, hd = x.shape
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


def layer(cfg: Dict[str, Any], lw: Dict[str, Any], x, quant=None):
    """One decoder layer over a whole sequence x: (S, hidden)."""
    S = x.shape[0]
    H = cfg["num_attention_heads"]
    KV = cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, lw["input_norm"], eps)
    q = (_einsum("sd,de->se", h, lw["q_w"], quant) + lw["q_b"]).reshape(S, H, hd)
    k = (_einsum("sd,de->se", h, lw["k_w"], quant) + lw["k_b"]).reshape(S, KV, hd)
    v = (_einsum("sd,de->se", h, lw["v_w"], quant) + lw["v_b"]).reshape(S, KV, hd)
    q = rope(q, cfg["rope_theta"])
    k = rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    scores = _einsum("qhd,khd->hqk", q, k, quant) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = _einsum("hqk,khd->qhd", p, v, quant).reshape(S, H * hd)
    x = x + _einsum("se,ed->sd", o, lw["o_w"], quant)
    h = rms_norm(x, lw["post_norm"], eps)
    g = _einsum("sd,df->sf", h, lw["gate_w"], quant)
    u = _einsum("sd,df->sf", h, lw["up_w"], quant)
    return x + _einsum("sf,fd->sd", jax.nn.silu(g) * u, lw["down_w"], quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_jit(cfg_items, lw, x, quant):
    return layer(dict(cfg_items), lw, x, quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _embed_jit(cfg_items, embed, tokens, quant):
    return fake_quant(embed, quant)[tokens]


@functools.partial(jax.jit, static_argnums=(0, 3))
def _final_jit(cfg_items, final_norm, x, quant):
    return rms_norm(x, final_norm, dict(cfg_items)["rms_norm_eps"])


def _cfg_items(cfg):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def hidden(w: Dict[str, Any], cfg: Dict[str, Any], tokens,
           quant: Optional[str] = None):
    """Final-normed hidden states (S, hidden) of one token sequence, one
    layer at a time, so that only one layer's activations are alive."""
    items = _cfg_items(cfg)
    x = _embed_jit(items, w["embed"], jnp.asarray(tokens, jnp.int32), quant)
    for i in range(cfg["num_hidden_layers"]):
        lw = jax.tree.map(lambda a, i=i: a[i], w["layers"])
        x = _layer_jit(items, lw, x, quant)
    return _final_jit(items, w["final_norm"], x, quant)


def logits(w: Dict[str, Any], h, quant: Optional[str] = None):
    """Rows of logits from final hidden states h: (rows, hidden)."""
    return _einsum("sd,vd->sv", h, w["embed"], quant)


def forward_logits(w, cfg, tokens, quant=None) -> np.ndarray:
    """Logits at every position of one sequence (small sizes only)."""
    return np.asarray(logits(w, hidden(w, cfg, tokens, quant), quant))


def loss(w, cfg, tokens, targets):
    """Mean next-token cross-entropy of one sequence (the training
    objective of the published model)."""
    lg = logits(w, hidden(w, cfg, tokens))
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, jnp.asarray(targets)[:, None], axis=-1)
    return jnp.mean(lse - gold[:, 0])
