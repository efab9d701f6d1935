"""Plain float32 DeepSeek-V2 decoder at one chip's share of its experts: the
yardstick that decides `correct`, and the `reference` module that
`configs/deepseek-v2-lite-ep8.json` names.

Written from the published description (DeepSeek-V2, arXiv:2405.04434, and
the `DeepseekV2ForCausalLM` config keys) in straightforward `jax.numpy`:
one sequence at a time, full expanded attention over the whole sequence,
no KV cache, no batching, no kernels, every matrix product at
`precision="highest"`. It imports nothing of the program under test and
takes its weights from `weights/deepseek_v2.py`'s `reference_weights`.
The harness calls `hidden` and `logits` (`check.py`).

Per layer:  x += MLA(n1(x));  x += MLP(n2(x))
MLA without query compression: q = x W_q per head, split into q_nope (128)
and q_pe (64); [c, k_pe] = x W_kva; k_nope, v = n_kv(c) W_kvb per head;
q_pe and the one k_pe shared by all heads take rope; scores
[q_nope, q_pe] . [k_nope, k_pe] times the softmax scale, causal softmax,
output (p v) W_o. Rope is YaRN (factor, original positions, beta_fast,
beta_slow, mscale, mscale_all_dim of `rope_scaling`) in HF's interleaved
layout: a rope slice is de-interleaved (view(d/2, 2).transpose) and then
rotated by halves. The softmax scale is (qk_nope + qk_rope)^-1/2 times
mscale(factor, mscale_all_dim)^2.
The MLP of the first `first_k_dense_replace` layers is a SwiGLU of width
`intermediate_size`; every later layer's is the MoE: softmax over the
router's `n_routed_experts_published` logits, greedy top-k
(`num_experts_per_tok`), the weights renormalised only if `norm_topk_prob`
and times `routed_scaling_factor`; each routed expert a SwiGLU of width
`moe_intermediate_size`; plus the shared experts, one SwiGLU of width
`moe_intermediate_size * n_shared_experts` that every token passes
through. RMSNorm n(x) = x / sqrt(mean(x^2) + eps) * w. The head is
`lm_head`, untied from the embedding.

Departures, all of which the configuration states: the MoE sums only the
`n_routed_experts` experts held here, router indices `expert_offset` and
up (the chip's share of expert parallelism; the other experts' part of the
result is left out, as on the chip); the layers are the configuration's
`num_hidden_layers`; weights are random, not the released checkpoint. The
auxiliary balance loss (`seq_aux`) is a training term and is left out.

`quant="fp8"` computes every matrix product (the projections, the two
attention products, the router, the MLPs, the head and the embedding
lookup) from operands rounded to float8 e4m3 with one absmax scale per
tensor, accumulated in float32: the lower-precision control of the
comparison (see `check.py`).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0
KEYS = ("num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rms_norm_eps", "rope_theta", "num_experts_per_tok",
        "n_routed_experts", "expert_offset", "norm_topk_prob",
        "routed_scaling_factor")


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


def _yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, base: float, rs: Dict[str, Any]):
    """(inverse frequencies (dim/2,), cos/sin magnitude) of YaRN, as
    DeepseekV2YarnRotaryEmbedding computes them."""
    def correction_dim(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / rs["factor"]
    mask = 1.0 - ramp
    inv = inter * (1 - mask) + extra * mask
    mscale = (_yarn_get_mscale(rs["factor"], rs.get("mscale", 1))
              / _yarn_get_mscale(rs["factor"], rs.get("mscale_all_dim", 0)))
    return inv, mscale


def softmax_scale(cfg) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg["rope_scaling"]
    if rs.get("mscale_all_dim"):
        scale *= _yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def rope_interleaved(x, inv_freq, mscale):
    """x: (S, heads, d) in HF's interleaved layout, positions 0..S-1."""
    S, heads, d = x.shape
    x = x.reshape(S, heads, d // 2, 2).swapaxes(-1, -2).reshape(S, heads, d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    cos, sin = jnp.cos(emb) * mscale, jnp.sin(emb) * mscale
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rotated * sin


def attention(cfg, lw, x, inv_freq, mscale, scale, quant=None):
    """MLA over a whole sequence x: (S, hidden), expanded form."""
    S = x.shape[0]
    H, R = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    q = _einsum("sd,de->se", x, lw["q_w"], quant).reshape(S, H, dn + dr)
    ckv = _einsum("sd,de->se", x, lw["kv_a_w"], quant)
    c = rms_norm(ckv[:, :R], lw["kv_a_norm"], cfg["rms_norm_eps"])
    kv = _einsum("sr,re->se", c, lw["kv_b_w"], quant).reshape(S, H, dn + dv)
    q_pe = rope_interleaved(q[..., dn:], inv_freq, mscale)
    k_pe = rope_interleaved(ckv[:, None, R:], inv_freq, mscale)
    qf = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    kf = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (S, H, dr))],
                         axis=-1)
    scores = _einsum("qhd,khd->hqk", qf, kf, quant) * scale
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = _einsum("hqk,khd->qhd", p, kv[..., dn:], quant).reshape(S, H * dv)
    return _einsum("se,ed->sd", o, lw["o_w"], quant)


def swiglu(x, gate, up, down, quant=None):
    g = _einsum("sd,df->sf", x, gate, quant)
    u = _einsum("sd,df->sf", x, up, quant)
    return _einsum("sf,fd->sd", jax.nn.silu(g) * u, down, quant)


def moe(cfg, mw, x, quant=None):
    """The held experts' part of the routed result plus the shared
    experts. x: (S, hidden)."""
    k, E, off = (cfg["num_experts_per_tok"], cfg["n_routed_experts"],
                 cfg["expert_offset"])
    scores = jax.nn.softmax(_einsum("sd,de->se", x, mw["router_w"], quant),
                            axis=-1)
    top_w, top_i = jax.lax.top_k(scores, k)
    if cfg["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * cfg["routed_scaling_factor"]
    # weight of held expert e for each token: its top-k weight, else 0
    held = jnp.arange(off, off + E)
    gate = jnp.sum(jnp.where(top_i[:, :, None] == held[None, None, :],
                             top_w[:, :, None], 0.0), axis=1)     # (S, E)
    g = _einsum("sd,edf->esf", x, mw["gate_w"], quant)
    u = _einsum("sd,edf->esf", x, mw["up_w"], quant)
    y = _einsum("esf,efd->esd", jax.nn.silu(g) * u, mw["down_w"], quant)
    routed = jnp.einsum("esd,se->sd", y, gate, precision=HIGHEST)
    return routed + swiglu(x, mw["shared_gate_w"], mw["shared_up_w"],
                           mw["shared_down_w"], quant)


def _layer_weights(w, i):
    return jax.tree.map(lambda a: a[i], w)


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _layer_jit(cfg_items, lw, mw, x, freq, scale, quant):
    """One layer; `mw` holds the dense MLP (gate_w, up_w, down_w) or the
    MoE's weights (router_w among them)."""
    cfg = dict(cfg_items)
    inv, msc = freq
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, lw, rms_norm(x, lw["input_norm"], eps), inv, msc,
                      scale, quant)
    h = rms_norm(x, lw["post_norm"], eps)
    if "router_w" in mw:
        return x + moe(cfg, mw, h, quant)
    return x + swiglu(h, mw["gate_w"], mw["up_w"], mw["down_w"], quant)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed_jit(embed, tokens, quant):
    return fake_quant(embed, quant)[tokens]


@jax.jit
def _final_jit(final_norm, x, eps):
    return rms_norm(x, final_norm, eps)


def hidden(w: Dict[str, Any], cfg: Dict[str, Any], tokens,
           quant: Optional[str] = None):
    """Final-normed hidden states (S, hidden) of one token sequence, one
    layer at a time, so that only one layer's activations are alive."""
    items = tuple((k, cfg[k]) for k in KEYS)
    inv, msc = yarn_inv_freq(cfg["qk_rope_head_dim"], cfg["rope_theta"],
                             cfg["rope_scaling"])
    freq = (jnp.asarray(inv, jnp.float32), jnp.float32(msc))
    x = _embed_jit(w["embed"], jnp.asarray(tokens, jnp.int32), quant)
    Ld = cfg["first_k_dense_replace"]
    for i in range(cfg["num_hidden_layers"]):
        mw = (_layer_weights(w["dense"], i) if i < Ld
              else _layer_weights(w["moe"], i - Ld))
        x = _layer_jit(items, _layer_weights(w["layers"], i), mw, x, freq,
                       softmax_scale(cfg), quant)
    return _final_jit(w["final_norm"], x, cfg["rms_norm_eps"])


def logits(w: Dict[str, Any], h, quant: Optional[str] = None):
    """Rows of logits from final hidden states h: (rows, hidden)."""
    return _einsum("sd,vd->sv", h, w["lm_head"], quant)


def forward_logits(w, cfg, tokens, quant=None) -> np.ndarray:
    """Logits at every position of one sequence (small sizes only)."""
    return np.asarray(logits(w, hidden(w, cfg, tokens, quant), quant))
