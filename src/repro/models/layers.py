"""Core layer library (pure functional JAX).

Conventions:
  * params are nested dicts of jnp arrays; a "stacked" layer dict has a
    leading ``num_layers`` axis on every leaf (consumed by lax.scan).
  * every init_* returns (params, logical_axes) where logical_axes mirrors
    params with tuples of logical axis names (see repro.sharding).
  * compute dtype = cfg.dtype (bf16 on TPU); master params = cfg.param_dtype.
  * attention is exact (einsum, f32 softmax); the Pallas flash kernel in
    repro.kernels is an alternative impl selected by cfg.attention_impl.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


def _dt(cfg):
    return jnp.dtype(cfg.dtype)


def _pdt(cfg):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def normal_init(key, shape, dtype, scale: float):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_linear(key, d_in: int, shape_out: Tuple[int, ...], dtype,
                scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return normal_init(key, (d_in, *shape_out), dtype, scale)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_tables(positions, head_dim: int, theta: float, scaling=()):
    """positions: (...,) int32 -> (sin, cos) of shape (..., head_dim//2), f32.
    `scaling` (ModelConfig.rope_scaling) applies YaRN's frequencies and
    magnitude."""
    half = head_dim // 2
    if scaling:
        freq, mscale = yarn_frequencies(head_dim, theta, dict(scaling))
    else:
        freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq  # (..., half)
    if scaling and mscale != 1.0:
        return jnp.sin(ang) * mscale, jnp.cos(ang) * mscale
    return jnp.sin(ang), jnp.cos(ang)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_ramp(head_dim: int, theta: float, s) -> Tuple[int, int]:
    """Rope pairs over which YaRN blends from the original frequencies (pairs
    below `low`) to the interpolated ones (pairs above `high`)."""
    def dim(rotations):
        return (head_dim * math.log(s["original_max_position_embeddings"]
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(dim(s["beta_fast"])), 0),
            min(math.ceil(dim(s["beta_slow"])), head_dim - 1))


def yarn_frequencies(head_dim: int, theta: float, s):
    """YaRN (arXiv:2309.00071, as DeepSeek-V2 configures it): per rope pair,
    the original frequency below the ramp, the frequency divided by `factor`
    above it, linearly blended across it; and the sin/cos magnitude
    mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    half = head_dim // 2
    extra = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim)
    low, high = yarn_ramp(head_dim, theta, s)
    ramp = np.clip((np.arange(half, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0, 1)
    freq = extra / s["factor"] * ramp + extra * (1 - ramp)
    mscale = (yarn_mscale(s["factor"], s.get("mscale", 1.0))
              / yarn_mscale(s["factor"], s.get("mscale_all_dim", 0.0)))
    return jnp.asarray(freq, jnp.float32), float(mscale)


def attention_scale(cfg) -> float:
    """Softmax scale: 1/sqrt(head size), times YaRN's mscale(factor,
    mscale_all_dim) squared where the configuration scales rope so."""
    if not cfg.kv_lora_rank:
        return 1.0 / math.sqrt(cfg.head_dim)
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    s = dict(cfg.rope_scaling)
    if s.get("mscale_all_dim"):
        scale *= yarn_mscale(s["factor"], s["mscale_all_dim"]) ** 2
    return scale


def apply_rope(x, sin, cos):
    """x: (B, S, H, hd); sin/cos: (S, hd//2) or broadcastable (B, S, hd//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if sin.ndim == 2:  # (S, half) -> broadcast over batch and heads
        sin = sin[None, :, None, :]
        cos = cos[None, :, None, :]
    else:              # (B, S, half)
        sin = sin[:, :, None, :]
        cos = cos[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1f, x2f = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention parameters
# ---------------------------------------------------------------------------

def init_attention(key, cfg, layers: Optional[int] = None):
    """GQA attention params; stacked over ``layers`` when given."""
    ks = jax.random.split(key, 8)
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    L = (layers,) if layers else ()
    pdt = _pdt(cfg)

    def mk(k, shape, fan_in):
        return normal_init(k, L + shape, pdt, 1.0 / math.sqrt(fan_in))

    p = {
        "wq": mk(ks[0], (D, H, hd), D),
        "wk": mk(ks[1], (D, KV, hd), D),
        "wv": mk(ks[2], (D, KV, hd), D),
        "wo": mk(ks[3], (H, hd, D), H * hd),
    }
    lax_pref = ("layers",) if layers else ()
    ax = {
        "wq": lax_pref + ("embed", "heads", "head_dim"),
        "wk": lax_pref + ("embed", "kv_heads", "head_dim"),
        "wv": lax_pref + ("embed", "kv_heads", "head_dim"),
        "wo": lax_pref + ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros(L + (H, hd), pdt)
        p["bk"] = jnp.zeros(L + (KV, hd), pdt)
        p["bv"] = jnp.zeros(L + (KV, hd), pdt)
        ax["bq"] = lax_pref + ("heads", "head_dim")
        ax["bk"] = lax_pref + ("kv_heads", "head_dim")
        ax["bv"] = lax_pref + ("kv_heads", "head_dim")
    return p, ax


def qkv_project(cfg, p, x):
    """x: (B, S, D) -> q (B,S,H,hd), k/v (B,S,KV,hd) in compute dtype."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return q, k, v


def out_project(cfg, p, o):
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))


# ---------------------------------------------------------------------------
# Latent attention (MLA, DeepSeek-V2 arXiv:2405.04434), no query compression
# ---------------------------------------------------------------------------

def init_mla(key, cfg, layers: Optional[int] = None):
    """MLA params: the query, the joint down-projection to the latent and the
    shared rope key (`wkv_a`), the latent's RMSNorm, the latent's per-head
    key and value up-projections (`wk_b`, `wv_b`) and the output."""
    ks = jax.random.split(key, 5)
    D, H, R = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    L = (layers,) if layers else ()
    pdt = _pdt(cfg)

    def mk(k, shape, fan_in):
        return normal_init(k, L + shape, pdt, 1.0 / math.sqrt(fan_in))

    p = {"wq": mk(ks[0], (D, H, dn + dr), D),
         "wkv_a": mk(ks[1], (D, R + dr), D),
         "kv_norm": jnp.zeros(L + (R,), pdt),
         "wk_b": mk(ks[2], (R, H, dn), R),
         "wv_b": mk(ks[3], (R, H, dv), R),
         "wo": mk(ks[4], (H, dv, D), H * dv)}
    lax_pref = ("layers",) if layers else ()
    ax = {"wq": lax_pref + ("embed", "heads", "head_dim"),
          "wkv_a": lax_pref + ("embed", None),
          "kv_norm": lax_pref + (None,),
          "wk_b": lax_pref + (None, "heads", "head_dim"),
          "wv_b": lax_pref + (None, "heads", "head_dim"),
          "wo": lax_pref + ("heads", "head_dim", "embed")}
    return p, ax


def mla_query(cfg, p, x, sin, cos):
    """x: (B,S,D) -> (q_nope (B,S,H,dn), roped q_pe (B,S,H,dr))."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    dn = cfg.qk_nope_head_dim
    return q[..., :dn], apply_rope(q[..., dn:], sin, cos)


def mla_latent(cfg, p, x, sin, cos):
    """x: (B,S,D) -> the latent cache rows of its tokens: the normed latent
    `c_kv` (B,S,R) and the roped key `k_pe` (B,S,dr) shared by all heads."""
    R = cfg.kv_lora_rank
    ckv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(x.dtype))
    c = rms_norm(ckv[..., :R], p["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope(ckv[..., None, R:], sin, cos)[:, :, 0]
    return {"c_kv": c, "k_pe": k_pe}


def mla_expand(p, q_nope, q_pe, rows):
    """Prefill form: up-project the latent into per-head keys and values.
    Returns (q, k, v) for the attention core: q, k (B,S,H,dn+dr) with the
    shared rope key broadcast over heads, v (B,S,H,dv)."""
    c = rows["c_kv"]
    k_nope = jnp.einsum("bsr,rhk->bshk", c, p["wk_b"].astype(c.dtype))
    v = jnp.einsum("bsr,rhk->bshk", c, p["wv_b"].astype(c.dtype))
    k_pe = jnp.broadcast_to(rows["k_pe"][:, :, None, :],
                            k_nope.shape[:3] + rows["k_pe"].shape[-1:])
    return (jnp.concatenate([q_nope, q_pe], axis=-1),
            jnp.concatenate([k_nope, k_pe.astype(k_nope.dtype)], axis=-1), v)


def mla_absorbed_attention(p, q_nope, q_pe, c_cache, pe_cache, pos,
                           scale: float):
    """Decode form over the latent cache (B,T,R) and rope-key cache (B,T,dr):
    W_uk folds into the query, q_lat = W_uk^T q_nope (B,1,H,R), so scores are
    q_lat . c + q_pe . k_pe; W_uv folds into the output, applied once to the
    attention-weighted latent. Positions > pos are masked; `pos` is a scalar
    or (B,), one position per row."""
    f32 = jnp.float32
    dt = q_nope.dtype
    T = c_cache.shape[1]
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wk_b"].astype(dt),
                       preferred_element_type=f32)
    cf = c_cache.astype(f32)
    logits = (jnp.einsum("bshr,btr->bhst", q_lat, cf)
              + jnp.einsum("bshk,btk->bhst", q_pe.astype(f32),
                           pe_cache.astype(f32))) * scale
    valid = _valid_rows(pos, T)
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhst,btr->bshr", w, cf)
    return jnp.einsum("bshr,rhk->bshk", ctx.astype(dt),
                      p["wv_b"].astype(dt))


def latent_cache_update(cache, rows, pos, *, layer=None):
    """Insert one token's latent rows ({c_kv, k_pe}: (B,1,...)) at `pos`
    (see `write_rows` for `pos` and `layer`)."""
    return {name: write_rows(cache[name], rows[name], pos, layer=layer)
            for name in cache}


def write_rows(cache, new, pos, *, layer=None):
    """Write one token's row of each batch row, `new` (B,1,...), into
    `cache` (B,T,...) or, given `layer`, into layer `layer` of a
    layer-stacked cache (L,B,T,...). `pos` is a scalar (every row at that
    position) or (B,) (row b at pos[b]). Only those B rows are written, so
    on a loop carry the write is in place. Positions clamp into [0, T) as
    `dynamic_update_slice` clamps them."""
    new = new.astype(cache.dtype)
    lead = () if layer is None else (layer,)
    if jnp.ndim(pos) == 0:
        start = lead + (0, pos) + (0,) * (new.ndim - 2)
        return jax.lax.dynamic_update_slice(
            cache, new if layer is None else new[None], start)
    rows = jnp.arange(new.shape[0])
    return cache.at[lead + (rows, pos)].set(new[:, 0], mode="clip")


def layer_view(cache, layer):
    """Layer `layer` of a layer-stacked cache {name: (L,B,T,...)}, or the
    cache itself when `layer` is None (it is one layer's already)."""
    if layer is None:
        return cache
    return {n: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for n, a in cache.items()}


def _valid_rows(pos, T: int, window: int = 0):
    """(B|1, T) mask of the cache positions a decode row attends: those at
    or below its position (`pos` scalar or (B,)). A ring buffer of
    T == window slots holds a live entry in every slot once pos+1 >= T."""
    p = jnp.reshape(pos, (-1, 1))
    valid = jnp.arange(T)[None, :] <= p
    if window:
        valid = jnp.logical_or(valid, p + 1 >= T)
    return valid


# ---------------------------------------------------------------------------
# Attention cores
# ---------------------------------------------------------------------------

def _gqa_scores(q, k, scale):
    """q: (B,S,H,hd) k: (B,T,KV,hd) -> logits (B,KV,G,S,T) in f32."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    return jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                      k.astype(jnp.float32)) * scale


def _gqa_out(w, v, out_dtype):
    """w: (B,KV,G,S,T) f32; v: (B,T,KV,hd) -> (B,S,H,hd)."""
    B, KV, G, S, T = w.shape
    o = jnp.einsum("bkgst,btkh->bskgh", w, v.astype(jnp.float32))
    return o.reshape(B, S, KV * G, v.shape[-1]).astype(out_dtype)


def causal_attention(q, k, v, *, causal: bool = True,
                     positions_q=None, positions_k=None, scale=None):
    """Exact attention with f32 softmax. q:(B,S,H,hd) k:(B,T,KV,hd)
    v:(B,T,KV,hd_v). `scale` defaults to 1/sqrt(hd)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    logits = _gqa_scores(q, k, scale)          # (B,KV,G,S,T)
    if causal:
        S, T = logits.shape[-2], logits.shape[-1]
        pq = positions_q if positions_q is not None else jnp.arange(S)
        pk = positions_k if positions_k is not None else jnp.arange(T)
        mask = pq[:, None] >= pk[None, :]
        logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return _gqa_out(w, v, q.dtype)


def sliding_window_attention(q, k, v, window: int):
    """Blocked local (sliding-window, causal) attention.

    Memory is O(S * 2w) instead of O(S^2): the sequence is cut into blocks of
    ``window`` and each block attends to itself + the previous block with the
    exact band mask. Requires S % window == 0 (all assigned shapes satisfy
    this; input_specs pads otherwise).
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    if S <= window:
        return causal_attention(q, k, v)
    assert S % window == 0, (S, window)
    nb = S // window
    scale = 1.0 / math.sqrt(hd)
    G = H // KV

    qb = q.reshape(B, nb, window, KV, G, hd)
    kb = k.reshape(B, nb, window, KV, hd)
    vb = v.reshape(B, nb, window, KV, hd)
    # previous block of k/v (block 0's "previous" is zeros, fully masked)
    k_prev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    v_prev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    kcat = jnp.concatenate([k_prev, kb], axis=2)   # (B, nb, 2w, KV, hd)
    vcat = jnp.concatenate([v_prev, vb], axis=2)

    logits = jnp.einsum("bnskgh,bntkh->bnkgst", qb.astype(jnp.float32),
                        kcat.astype(jnp.float32)) * scale  # (B,nb,KV,G,w,2w)
    qpos = jnp.arange(window)[:, None] + window          # query pos within [w, 2w)
    kpos = jnp.arange(2 * window)[None, :]               # key pos within [0, 2w)
    band = (qpos >= kpos) & (qpos - kpos < window)       # causal & within window
    first = (jnp.arange(nb) == 0)[:, None, None]         # block 0 has no prev block
    mask = band[None, :, :] & ~(first & (kpos < window)[None, :, :])  # (nb, w, 2w)
    logits = jnp.where(mask[None, :, None, None, :, :], logits, -1e30)
    w_ = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bnkgst,bntkh->bnskgh", w_, vcat.astype(jnp.float32))
    return o.reshape(B, S, H, hd).astype(q.dtype)


_CHUNK_Q = 512           # default q-chunk for the XLA flash path
_CHUNK_K = 1024
CHUNKED_THRESHOLD = 2048  # use chunked attention when S exceeds this


def _flash_kv_body(carry, xs, scale):
    """Inner (k-block) step of XLA-expressed flash attention — also a
    dry-run cost probe. carry=(m,l,acc); xs=(k_blk,v_blk,s_blk,q_blk,qpos)."""
    m, l, acc = carry
    kb, vb, kpos, qb, qpos = xs
    s = jnp.einsum("bhqd,bhkd->bhqk", qb.astype(jnp.float32),
                   kb.astype(jnp.float32)) * scale
    mask = qpos[..., :, None] >= kpos[..., None, :]
    s = jnp.where(mask[:, None], s, -1e30)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
    return (m_new, l_new, acc_new), None


def chunked_causal_attention(q, k, v, *, q_chunk: int = _CHUNK_Q,
                             k_chunk: int = _CHUNK_K, scale=None):
    """Flash attention expressed in XLA scans (GSPMD-shardable): outer scan
    over q chunks, inner scan over k chunks, online-softmax carry. Memory is
    O(q_chunk * k_chunk) per step instead of O(S^2).

    q: (B,S,H,hd); k/v: (B,S,KV,hd). Exact vs mha oracle. NB: the inner scan
    visits every k block (no causal block skipping in XLA) — the compiled
    FLOPs overcount causal attention ~2x; the roofline report corrects for
    this analytically and the Pallas kernel path skips for real on TPU.
    Values narrower than the keys (MLA) are zero-padded to their width and
    the output cut back. `scale` defaults to 1/sqrt(hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    hd_v = v.shape[-1]
    if hd_v != hd:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, hd - hd_v)))
    qc = min(q_chunk, S)
    kc = min(k_chunk, S)
    pS = (-S) % qc
    pK = (-S) % kc

    # head-major layout, GQA expanded per q head group index
    qt = q.transpose(0, 2, 1, 3)                               # (B,H,S,hd)
    kt = k.transpose(0, 2, 1, 3)                               # (B,KV,S,hd)
    vt = v.transpose(0, 2, 1, 3)
    if pS:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pS), (0, 0)))
    if pK:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pK), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pK), (0, 0)))
    Sq, Sk = qt.shape[2], kt.shape[2]
    nq, nk = Sq // qc, Sk // kc

    kb = kt.reshape(B, KV, nk, kc, hd).transpose(2, 0, 1, 3, 4)  # (nk,B,KV,kc,hd)
    vb = vt.reshape(B, KV, nk, kc, hd).transpose(2, 0, 1, 3, 4)
    kpos = (jnp.arange(Sk).reshape(nk, 1, kc)
            + jnp.zeros((nk, B, kc), jnp.int32))                  # (nk,B,kc)
    kpos = jnp.where(kpos < S, kpos, jnp.int32(2**30))            # pad = +inf pos

    def q_body(_, qxs):
        qblk, qpos = qxs                                          # (B,H,qc,hd)
        qg = qblk.reshape(B, KV, G, qc, hd).reshape(B, KV * G, qc, hd)
        m0 = jnp.full((B, H, qc), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, qc), jnp.float32)
        a0 = jnp.zeros((B, H, qc, hd), jnp.float32)

        def kv_body(carry, kxs):
            kblk, vblk, kp = kxs
            kg = jnp.repeat(kblk, G, axis=1)                      # (B,H,kc,hd)
            vg = jnp.repeat(vblk, G, axis=1)
            return _flash_kv_body(carry, (kg, vg, kp, qg, qpos), scale)

        # flash bwd semantics: recompute p in backward instead of saving the
        # (qc, kc) probability tiles per step (otherwise the scan stashes the
        # full S^2 matrix as residuals and the memory win evaporates)
        kv_body = jax.checkpoint(
            kv_body, policy=jax.checkpoint_policies.nothing_saveable)
        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0), (kb, vb, kpos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    qblocks = qt.reshape(B, H, nq, qc, hd).transpose(2, 0, 1, 3, 4)
    qpos = (jnp.arange(Sq).reshape(nq, 1, qc)
            + jnp.zeros((nq, B, qc), jnp.int32))
    q_body = jax.checkpoint(
        q_body, policy=jax.checkpoint_policies.nothing_saveable)
    _, outs = jax.lax.scan(q_body, None, (qblocks, qpos))          # (nq,B,H,qc,hd)
    out = outs.transpose(1, 2, 0, 3, 4).reshape(B, H, Sq, hd)
    return out[:, :, :S, :hd_v].transpose(0, 2, 1, 3)


def chunked_window_attention(q, k, v, window: int, *, q_chunk: int = _CHUNK_Q):
    """Exact sliding-window attention, linear in S: each q chunk attends to a
    statically-sized k slice [chunk_start - window, chunk_end). No masked-out
    block overcount (the slice is exactly the live range)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qc = min(q_chunk, S)
    pS = (-S) % qc
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if pS:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pS), (0, 0)))
    Sq = qt.shape[2]
    nq = Sq // qc
    span = window + qc                       # k live range per q chunk
    # left-pad k/v by `window` so the slice start is simply i*qc
    ktp = jnp.pad(kt, ((0, 0), (0, 0), (window, pS), (0, 0)))
    vtp = jnp.pad(vt, ((0, 0), (0, 0), (window, pS), (0, 0)))

    def q_body(_, xs):
        i = xs
        qblk = jax.lax.dynamic_slice_in_dim(qt, i * qc, qc, axis=2)
        kblk = jax.lax.dynamic_slice_in_dim(ktp, i * qc, span, axis=2)
        vblk = jax.lax.dynamic_slice_in_dim(vtp, i * qc, span, axis=2)
        qpos = i * qc + jnp.arange(qc)
        kpos = i * qc - window + jnp.arange(span)
        qg = qblk.reshape(B, KV, G, qc, hd)
        s = jnp.einsum("bkgqd,bkcd->bkgqc", qg.astype(jnp.float32),
                       kblk.astype(jnp.float32)) * scale
        mask = ((qpos[:, None] >= kpos[None, :])
                & (qpos[:, None] - kpos[None, :] < window)
                & (kpos[None, :] >= 0) & (qpos[:, None] < S))
        s = jnp.where(mask[None, None, None], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqc,bkcd->bkgqd", w, vblk.astype(jnp.float32))
        return None, o.reshape(B, H, qc, hd).astype(q.dtype)

    q_body = jax.checkpoint(
        q_body, policy=jax.checkpoint_policies.nothing_saveable)
    _, outs = jax.lax.scan(q_body, None, jnp.arange(nq))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(B, H, Sq, hd)
    return out[:, :, :S, :].transpose(0, 2, 1, 3)


# -- dry-run cost probes for the chunked-attention scan bodies ----------------
# (cost-equivalent mirrors of the scan bodies above: same einsum/mask shapes,
#  so compiled FLOPs/bytes match the in-loop bodies exactly)

def flash_kvbody_probe(m, l, acc, kblk, vblk, kp, qblk, qpos):
    """One inner (k-block) step incl. the GQA repeat. kblk: (B,KV,kc,hd);
    qblk: (B,H,qc,hd)."""
    G = qblk.shape[1] // kblk.shape[1]
    kg = jnp.repeat(kblk, G, axis=1)
    vg = jnp.repeat(vblk, G, axis=1)
    scale = 1.0 / math.sqrt(qblk.shape[-1])
    (m2, l2, a2), _ = _flash_kv_body((m, l, acc), (kg, vg, kp, qblk, qpos), scale)
    return m2, l2, a2


def flash_qbody_probe(qblk, kb, vb, kpos, qpos):
    """One outer (q-chunk) step: inner scan over all k blocks (counted once
    by HLO cost analysis, exactly like the real program's nesting).
    kb: (nk,B,KV,kc,hd)."""
    B, H, qc, hd = qblk.shape
    KV = kb.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    m0 = jnp.full((B, H, qc), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, qc), jnp.float32)
    a0 = jnp.zeros((B, H, qc, hd), jnp.float32)

    def kv_body(carry, kxs):
        kblk, vblk, kp = kxs
        kg = jnp.repeat(kblk, G, axis=1)
        vg = jnp.repeat(vblk, G, axis=1)
        return _flash_kv_body(carry, (kg, vg, kp, qblk, qpos), scale)

    (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0), (kb, vb, kpos))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(qblk.dtype)


def window_qbody_probe(qt, ktp, vtp, idx, window: int):
    """One q-chunk step of chunked_window_attention. qt: (B,H,Sq,hd);
    ktp/vtp: (B,KV,Sq+window,hd) (pre-padded)."""
    B, H, Sq, hd = qt.shape
    KV = ktp.shape[1]
    G = H // KV
    qc = min(_CHUNK_Q, Sq)
    span = window + qc
    scale = 1.0 / math.sqrt(hd)
    qblk = jax.lax.dynamic_slice_in_dim(qt, idx * qc, qc, axis=2)
    kblk = jax.lax.dynamic_slice_in_dim(ktp, idx * qc, span, axis=2)
    vblk = jax.lax.dynamic_slice_in_dim(vtp, idx * qc, span, axis=2)
    qpos = idx * qc + jnp.arange(qc)
    kpos = idx * qc - window + jnp.arange(span)
    qg = qblk.reshape(B, KV, G, qc, hd)
    s = jnp.einsum("bkgqd,bkcd->bkgqc", qg.astype(jnp.float32),
                   kblk.astype(jnp.float32)) * scale
    mask = ((qpos[:, None] >= kpos[None, :])
            & (qpos[:, None] - kpos[None, :] < window) & (kpos[None, :] >= 0))
    s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqc,bkcd->bkgqd", w, vblk.astype(jnp.float32))
    return o.reshape(B, H, qc, hd).astype(qt.dtype)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0):
    """Single-token decode. q: (B,1,H,hd); caches: (B,T,KV,hd); pos: int32
    scalar or (B,), each row's current position (0-based). ``window>0`` ->
    ring-buffer cache of size ``window`` (local attention)."""
    B, _, H, hd = q.shape
    T = k_cache.shape[1]
    scale = 1.0 / math.sqrt(hd)
    KV = k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale  # (B,KV,G,1,T)
    # The current token is written to its slot (pos % window under a
    # window) *before* attention, so it attends to itself.
    valid = _valid_rows(pos, T, window)
    logits = jnp.where(valid[:, None, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", w, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def decode_attention_rows(q, k_rows, v_rows, pos, *, window: int = 0):
    """Single-token decode over caches of one row per token, (B,T,KV*hd),
    each row the token's KV heads side by side. Each query head is laid
    over the whole row, zero outside its own KV head's span, so that both
    contractions run over the row's lanes as the cache holds them, with no
    relayout of the cache, at KV times the multiply-adds. q: (B,1,H,hd);
    pos and ``window`` as in `decode_attention`."""
    f32 = jnp.float32
    B, _, H, hd = q.shape
    T, C = k_rows.shape[1], k_rows.shape[2]
    KV = C // hd
    own = jnp.repeat(jnp.eye(KV, dtype=f32), H // KV, axis=0)   # (H, KV)
    qx = (q[:, 0, :, None, :].astype(f32)
          * own[None, :, :, None]).reshape(B, H, C)
    logits = jnp.einsum("bhc,btc->bht", qx, k_rows.astype(f32)) * (
        1.0 / math.sqrt(hd))
    logits = jnp.where(_valid_rows(pos, T, window)[:, None, :], logits,
                       -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bht,btc->bhc", w, v_rows.astype(f32))
    o = jnp.einsum("bhkd,hk->bhd", o.reshape(B, H, KV, hd), own)
    return o[:, None].astype(q.dtype)


def cache_update(k_cache, v_cache, k_new, v_new, pos, *, window: int = 0,
                 layer=None):
    """Insert one token's k/v at ``pos`` (ring slot ``pos % window`` if
    local); see `write_rows` for ``pos`` and ``layer``."""
    slot = pos % window if window else pos
    return (write_rows(k_cache, k_new, slot, layer=layer),
            write_rows(v_cache, v_new, slot, layer=layer))


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, layers: Optional[int] = None,
             d_ff: Optional[int] = None):
    D, F = cfg.d_model, d_ff or cfg.d_ff
    L = (layers,) if layers else ()
    lax_pref = ("layers",) if layers else ()
    pdt = _pdt(cfg)
    ks = jax.random.split(key, 3)
    if cfg.mlp_act == "swiglu":
        p = {
            "w_gate": normal_init(ks[0], L + (D, F), pdt, 1.0 / math.sqrt(D)),
            "w_up":   normal_init(ks[1], L + (D, F), pdt, 1.0 / math.sqrt(D)),
            "w_down": normal_init(ks[2], L + (F, D), pdt, 1.0 / math.sqrt(F)),
        }
        ax = {
            "w_gate": lax_pref + ("embed", "mlp"),
            "w_up":   lax_pref + ("embed", "mlp"),
            "w_down": lax_pref + ("mlp", "embed"),
        }
    else:
        p = {
            "w_up":   normal_init(ks[0], L + (D, F), pdt, 1.0 / math.sqrt(D)),
            "b_up":   jnp.zeros(L + (F,), pdt),
            "w_down": normal_init(ks[1], L + (F, D), pdt, 1.0 / math.sqrt(F)),
            "b_down": jnp.zeros(L + (D,), pdt),
        }
        ax = {
            "w_up":   lax_pref + ("embed", "mlp"),
            "b_up":   lax_pref + ("mlp",),
            "w_down": lax_pref + ("mlp", "embed"),
            "b_down": lax_pref + ("embed",),
        }
    return p, ax


def mlp(cfg, p, x):
    dt = x.dtype
    if cfg.mlp_act == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
        return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))
    h = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt)) + p["b_up"].astype(dt)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(dt)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt)) + p["b_down"].astype(dt)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def init_embedding(key, cfg):
    pdt = _pdt(cfg)
    ks = jax.random.split(key, 2)
    p = {"tok": normal_init(ks[0], (cfg.vocab_size, cfg.d_model), pdt, 0.02)}
    ax = {"tok": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        p["head"] = normal_init(ks[1], (cfg.d_model, cfg.vocab_size), pdt,
                                1.0 / math.sqrt(cfg.d_model))
        ax["head"] = ("embed", "vocab")
    return p, ax


def embed_tokens(cfg, emb_p, tokens):
    return jnp.take(emb_p["tok"], tokens, axis=0).astype(_dt(cfg))


def logits_from_hidden(cfg, emb_p, h):
    if cfg.tie_embeddings:
        w = emb_p["tok"].astype(h.dtype)  # (V, D)
        return jnp.einsum("bsd,vd->bsv", h, w)
    return jnp.einsum("bsd,dv->bsv", h, emb_p["head"].astype(h.dtype))


def cross_entropy_loss(logits, targets, *, z_loss: float = 1e-4):
    """Token-mean CE with optional z-loss; logits may be vocab-sharded
    (GSPMD inserts the collective for the logsumexp)."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    loss = jnp.mean(nll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(lse * lse)
    return loss


CE_CHUNK = 512      # seq chunk for the streamed head+CE path


def ce_chunk_body(carry, xs, w_or_emb, tied: bool):
    """One seq-chunk of the streamed cross-entropy (scan body + cost probe).

    Computes the head projection AND the CE for one chunk so the full
    (B, S, V) logits tensor never materializes — the production fix for the
    vocab-memory blowup (DESIGN.md §7). carry=(nll_sum, z_sum);
    xs=(h_chunk (B,c,D), tgt_chunk (B,c), valid (B,c))."""
    nll_sum, z_sum = carry
    h, tgt, valid = xs
    if tied:
        logits = jnp.einsum("bcd,vd->bcv", h, w_or_emb.astype(h.dtype))
    else:
        logits = jnp.einsum("bcd,dv->bcv", h, w_or_emb.astype(h.dtype))
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, tgt[..., None], axis=-1)[..., 0]
    m = valid.astype(jnp.float32)
    return (nll_sum + jnp.sum((lse - gold) * m),
            z_sum + jnp.sum(lse * lse * m)), None


def chunked_cross_entropy(cfg, emb_p, h, targets, *, chunk: int = CE_CHUNK,
                          z_loss: float = 1e-4):
    """Streamed head+CE over seq chunks. h: (B,S,D); targets: (B,S)."""
    B, S, D = h.shape
    c = min(chunk, S)
    pS = (-S) % c
    if pS:
        h = jnp.pad(h, ((0, 0), (0, pS), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pS)))
    n = h.shape[1] // c
    hs = h.reshape(B, n, c, D).transpose(1, 0, 2, 3)
    ts = targets.reshape(B, n, c).transpose(1, 0, 2)
    valid = ((jnp.arange(h.shape[1]) < S).reshape(n, 1, c)
             + jnp.zeros((n, B, c), bool))
    w = emb_p["tok"] if cfg.tie_embeddings else emb_p["head"]

    def body(carry, xs):
        return ce_chunk_body(carry, xs, w, cfg.tie_embeddings)

    # recompute the chunk logits in backward — never stash (B,c,V) residuals
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (nll_sum, z_sum), _ = jax.lax.scan(
        body, (jnp.float32(0), jnp.float32(0)), (hs, ts, valid))
    n_tok = B * S
    loss = nll_sum / n_tok
    if z_loss:
        loss = loss + z_loss * (z_sum / n_tok)
    return loss
