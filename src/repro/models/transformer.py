"""Decoder LM assembly: scan-over-layers, all families, train + prefill + decode.

Families:
  dense / moe / vlm : homogeneous layer stack, lax.scan over L stacked params.
  hybrid (griffin)  : pattern groups (rec, rec, attn) scanned over G + tail.
  ssm (xlstm)       : pattern groups (mlstm, slstm) scanned over G.

Scan-over-layers keeps compile time depth-independent (critical for the 88-L
dry-runs on the CPU container) and is the production choice anyway.

Activation sharding hints are applied through an optional ``ctx`` (ShardCtx);
with ctx=None the code is mesh-free (CPU smoke tests).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as nn
from repro.models import moe as moe_lib
from repro.models import recurrent as rec_lib
from repro.models import xlstm as xlstm_lib


# ---------------------------------------------------------------------------
# Sharding context for activations
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardCtx:
    mesh: Any
    resolver: Any   # repro.sharding.Resolver

    def act(self, x, *logical):
        from jax.sharding import NamedSharding
        spec = self.resolver.spec(logical, x.shape, name="act")
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def tp_size(self) -> int:
        r = self.resolver.rules
        return r.axis_size(self.mesh, r.model_axes)


def _act(ctx, x, *logical):
    return ctx.act(x, *logical) if ctx is not None else x


# ---------------------------------------------------------------------------
# Layer init (per family)
# ---------------------------------------------------------------------------

def _init_dense_layer_stack(key, cfg, L, dense_mlp: bool = False):
    """L stacked layers: attention (MLA where configured) and the MoE MLP
    of the moe family, or a dense MLP (`dense_mlp`: the leading dense
    layers, `dense_d_ff` wide)."""
    ks = jax.random.split(key, 4)
    init_attn = nn.init_mla if cfg.kv_lora_rank else nn.init_attention
    attn_p, attn_ax = init_attn(ks[0], cfg, layers=L)
    if cfg.family == "moe" and cfg.num_experts and not dense_mlp:
        mlp_p, mlp_ax = moe_lib.init_moe(ks[1], cfg, layers=L)
    else:
        mlp_p, mlp_ax = nn.init_mlp(
            ks[1], cfg, layers=L,
            d_ff=cfg.dense_d_ff if dense_mlp else None)
    pdt = jnp.dtype(cfg.param_dtype)
    p = {"attn": attn_p, "mlp": mlp_p,
         "ln1": jnp.zeros((L, cfg.d_model), pdt),
         "ln2": jnp.zeros((L, cfg.d_model), pdt)}
    ax = {"attn": attn_ax, "mlp": mlp_ax,
          "ln1": ("layers", "embed"), "ln2": ("layers", "embed")}
    return p, ax


def _init_hybrid_group_stack(key, cfg, pattern, G):
    """One stacked group of blocks following ``pattern`` (e.g. rec,rec,attn)."""
    p, ax = {}, {}
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 2 * len(pattern))
    for i, kind in enumerate(pattern):
        name = f"b{i}_{kind}"
        if kind == "attention":
            bp, bax = nn.init_attention(ks[2 * i], cfg, layers=G)
        elif kind == "recurrent":
            bp, bax = rec_lib.init_recurrent_block(ks[2 * i], cfg, layers=G)
        elif kind == "mlstm":
            bp, bax = xlstm_lib.init_mlstm_block(ks[2 * i], cfg, layers=G)
        elif kind == "slstm":
            bp, bax = xlstm_lib.init_slstm_block(ks[2 * i], cfg, layers=G)
        else:
            raise ValueError(kind)
        entry = {"core": bp, "ln": jnp.zeros((G, cfg.d_model), pdt)}
        entry_ax = {"core": bax, "ln": ("layers", "embed")}
        if kind in ("attention", "recurrent") and cfg.d_ff:
            mp, max_ = nn.init_mlp(ks[2 * i + 1], cfg, layers=G)
            entry["mlp"] = mp
            entry["ln2"] = jnp.zeros((G, cfg.d_model), pdt)
            entry_ax["mlp"] = max_
            entry_ax["ln2"] = ("layers", "embed")
        p[name] = entry
        ax[name] = entry_ax
    return p, ax


def init_lm(key, cfg):
    """Returns (params, logical_axes)."""
    ks = jax.random.split(key, 4)
    emb_p, emb_ax = nn.init_embedding(ks[0], cfg)
    pdt = jnp.dtype(cfg.param_dtype)
    params: Dict[str, Any] = {"embed": emb_p,
                              "final_ln": jnp.zeros((cfg.d_model,), pdt)}
    axes: Dict[str, Any] = {"embed": emb_ax, "final_ln": ("embed",)}

    if cfg.block_pattern:
        pat = tuple(cfg.block_pattern)
        G = cfg.num_layers // len(pat)
        tail_len = cfg.num_layers - G * len(pat)
        gp, gax = _init_hybrid_group_stack(ks[1], cfg, pat, G)
        params["groups"] = gp
        axes["groups"] = gax
        if tail_len:
            tp, tax = _init_hybrid_group_stack(ks[2], cfg, pat[:tail_len], 1)
            params["tail"] = tp
            axes["tail"] = tax
    else:
        Ld = _dense_lead(cfg)
        if Ld:
            dp, dax = _init_dense_layer_stack(ks[3], cfg, Ld, dense_mlp=True)
            params["dense_layers"] = dp
            axes["dense_layers"] = dax
        lp, lax_ = _init_dense_layer_stack(ks[1], cfg, cfg.num_layers - Ld)
        params["layers"] = lp
        axes["layers"] = lax_
    return params, axes


def _dense_lead(cfg) -> int:
    """Leading dense-MLP layers before the MoE stack (moe family only)."""
    return cfg.first_dense_layers if cfg.family == "moe" else 0


def _rope(cfg, positions):
    return nn.rope_tables(positions, cfg.rope_dim, cfg.rope_theta,
                          cfg.rope_scaling)


# ---------------------------------------------------------------------------
# Block applications (full-sequence mode)
# ---------------------------------------------------------------------------

def _attn_full(cfg, lp, x, sin, cos, ctx, window: int = 0):
    """Pre-norm attention sub-block, full sequence. Under MLA the latent is
    up-projected into per-head keys and values (the expanded form)."""
    h = nn.rms_norm(x, lp["ln1"] if "ln1" in lp else lp["ln"], cfg.norm_eps)
    # sequence-parallel boundary: x stays seq-sharded, the norm runs locally
    # (per-token), and the all-gather moves the bf16 normed activations
    h = _act(ctx, h, "batch", None, None)
    if cfg.kv_lora_rank:
        ap = lp["attn"]
        q_nope, q_pe = nn.mla_query(cfg, ap, h, sin, cos)
        q, k, v = nn.mla_expand(ap, q_nope, q_pe,
                                nn.mla_latent(cfg, ap, h, sin, cos))
        o = _attention_dispatch(cfg, q, k, v, window)
        return x + _act(ctx, nn.out_project(cfg, ap, o), "batch", "seq", None)
    q, k, v = nn.qkv_project(cfg, lp["attn"] if "attn" in lp else lp["core"], h)
    q = nn.apply_rope(q, sin, cos)
    k = nn.apply_rope(k, sin, cos)
    # Inside attention: tensor-parallel over heads; when heads % TP != 0 the
    # batch dim takes data*model instead (fully-local attention). The q and
    # k/v layouts are COUPLED: if q shards heads, k/v either shard kv_heads
    # (divisible) or replicate over the model axis (GQA kv < TP: each kv head
    # lives on H/KV devices — the standard replication trick); k/v must never
    # take a batch layout different from q's. head_dim is deliberately NOT a
    # candidate for activations: it is a contraction dim, and sharding it
    # turns every QK^T/PV einsum into an S^2-sized all-reduce. The seq dim
    # must not pick up the model axis here either (attention chunking
    # reshapes seq -> replicate-repartition storms).
    tp = ctx.tp_size() if ctx is not None else 1
    heads_ok = q.shape[2] % tp == 0
    kv_ok = k.shape[2] % tp == 0
    if heads_ok:
        q = _act(ctx, q, "batch", None, "heads", None)
        kv_name = "kv_heads" if kv_ok else None
        k = _act(ctx, k, "batch", None, kv_name, None)
        v = _act(ctx, v, "batch", None, kv_name, None)
    else:
        q = _act(ctx, q, "batch_dm", None, None, None)
        k = _act(ctx, k, "batch_dm", None, None, None)
        v = _act(ctx, v, "batch_dm", None, None, None)
    o = _attention_dispatch(cfg, q, k, v, window)
    o = nn.out_project(cfg, lp["attn"] if "attn" in lp else lp["core"], o)
    return x + _act(ctx, o, "batch", "seq", None)


def _attention_dispatch(cfg, q, k, v, window: int = 0):
    """Pick the attention implementation by sequence length / config.

    S <= CHUNKED_THRESHOLD: exact einsum (O(S^2) logits, fine at this size).
    Larger S: flash-in-XLA chunked scans (O(chunk) memory, GSPMD-shardable).
    attention_impl="pallas": the Pallas flash kernel (TPU production path)."""
    S = q.shape[1]
    if cfg.kv_lora_rank:
        scale = nn.attention_scale(cfg)
        if S > nn.CHUNKED_THRESHOLD:
            return nn.chunked_causal_attention(q, k, v, scale=scale)
        return nn.causal_attention(q, k, v, scale=scale)
    if cfg.attention_impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=True, window=window)
    if window and S > window:
        return nn.chunked_window_attention(q, k, v, window)
    if S > nn.CHUNKED_THRESHOLD:
        return nn.chunked_causal_attention(q, k, v)
    return nn.causal_attention(q, k, v)


def _mlp_sub(cfg, lp, x, ctx, ln_key="ln2", mlp_key="mlp"):
    h = nn.rms_norm(x, lp[ln_key], cfg.norm_eps)
    h = _act(ctx, h, "batch", None, None)   # SP boundary (see _attn_full)
    if cfg.family == "moe" and mlp_key == "mlp" and cfg.num_experts and "router" in lp[mlp_key]:
        o, aux = moe_lib.moe_mlp(cfg, lp[mlp_key], h, ctx=ctx)
    else:
        o, aux = nn.mlp(cfg, lp[mlp_key], h), {}
    return x + _act(ctx, o, "batch", "seq", None), aux


def _dense_layer_full(cfg, lp, x, sin, cos, ctx):
    x = _attn_full(cfg, lp, x, sin, cos, ctx)
    x, aux = _mlp_sub(cfg, lp, x, ctx)
    return x, aux


def _hybrid_group_full(cfg, gp, x, sin, cos, ctx, pattern):
    """Apply one (stack-sliced) pattern group, full sequence. Returns (x, aux)."""
    auxes = {}
    for i, kind in enumerate(pattern):
        lp = gp[f"b{i}_{kind}"]
        if kind == "attention":
            x = _attn_full(cfg, {"ln1": lp["ln"], "attn": lp["core"]},
                           x, sin, cos, ctx, window=cfg.window_size)
            if "mlp" in lp:
                x, _ = _mlp_sub(cfg, lp, x, ctx)
        elif kind == "recurrent":
            h = nn.rms_norm(x, lp["ln"], cfg.norm_eps)
            h = _act(ctx, h, "batch", None, None)
            o, _ = rec_lib.recurrent_block(cfg, lp["core"], h)
            x = x + _act(ctx, o, "batch", "seq", None)
            if "mlp" in lp:
                x, _ = _mlp_sub(cfg, lp, x, ctx)
        elif kind == "mlstm":
            h = nn.rms_norm(x, lp["ln"], cfg.norm_eps)
            h = _act(ctx, h, "batch", None, None)
            o, _ = xlstm_lib.mlstm_block(cfg, lp["core"], h)
            x = x + _act(ctx, o, "batch", "seq", None)
        elif kind == "slstm":
            h = nn.rms_norm(x, lp["ln"], cfg.norm_eps)
            h = _act(ctx, h, "batch", None, None)
            o, _ = xlstm_lib.slstm_block(cfg, lp["core"], h)
            x = x + _act(ctx, o, "batch", "seq", None)
    return x, auxes


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        policy = jax.checkpoint_policies.nothing_saveable
    else:
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------------------
# Full forward (train / prefill trunk)
# ---------------------------------------------------------------------------

def lm_hidden(cfg, params, tokens, ctx=None, frontend_embeds=None,
              collect_kv: bool = False, stats: bool = False):
    """tokens: (B, S_text) int32. frontend_embeds: (B, P, D) or None.

    Returns (hidden (B,S,D), kv_stack or None, aux dict). S = P + S_text.
    kv_stack (dense families only): the decode cache's leaves over every
    layer, each (L, B, S, ...): {k, v} (L, B, S, KV, hd), or under MLA the
    latent rows {c_kv, k_pe}. `stats` adds `moe_held` (B, S) to aux: each
    token's routes to held experts, summed over the MoE layers."""
    x = nn.embed_tokens(cfg, params["embed"], tokens)
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    B, S, D = x.shape
    x = _act(ctx, x, "batch", "seq", None)
    sin, cos = _rope(cfg, jnp.arange(S))
    aux_out: Dict[str, Any] = {}

    if cfg.block_pattern:
        pat = tuple(cfg.block_pattern)
        G = cfg.num_layers // len(pat)

        def gbody(carry, gp):
            y, _ = _hybrid_group_full(cfg, gp, carry, sin, cos, ctx, pat)
            return y, None

        x, _ = jax.lax.scan(_remat(cfg, gbody), x, params["groups"])
        if "tail" in params:
            tail_pat = pat[: len(_pattern_tail(cfg))]

            def tbody(carry, gp):
                y, _ = _hybrid_group_full(cfg, gp, carry, sin, cos, ctx, tail_pat)
                return y, None

            x, _ = jax.lax.scan(_remat(cfg, tbody), x, params["tail"])
        kv = None
    else:
        def body(carry, lp):
            y, aux = _dense_layer_full(cfg, lp, carry, sin, cos, ctx)
            out = {}
            if collect_kv:
                # re-derive this layer's cache rows from the *input*
                # activations to seed the decode cache (prefill path only)
                hq = nn.rms_norm(carry, lp["ln1"], cfg.norm_eps)
                out["kv"] = _cache_rows(cfg, lp["attn"], hq, sin, cos)
            if not stats:
                aux.pop("moe_held", None)
            if aux and (stats or not collect_kv):
                out["aux"] = aux
            return y, out or None

        ys = []
        if "dense_layers" in params:
            x, y_dense = jax.lax.scan(_remat(cfg, body), x,
                                      params["dense_layers"])
            ys.append(y_dense)
        G = remat_group_size(cfg) if "dense_layers" not in params else 1
        if collect_kv or G == 1:
            x, y_main = jax.lax.scan(_remat(cfg, body), x, params["layers"])
        else:
            # scan-of-scans remat: checkpoint GROUPS of G layers so the
            # saved residual-stream carries shrink L -> L/G (the standard
            # sqrt-style activation-checkpointing trade; bwd recomputes one
            # group forward). Only the bwd path cares, so prefill keeps the
            # flat scan.
            NG = cfg.num_layers // G
            grouped = jax.tree.map(
                lambda a: a.reshape((NG, G) + a.shape[1:]), params["layers"])

            # two-level remat: the inner per-layer body is checkpointed as
            # well, otherwise the group's bwd recompute stashes G layers of
            # f32 residuals (norm/silu upcasts) at once — the difference
            # between ~240 GB and ~10 GB per device at 123B/1M-token scale
            inner = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)

            def group_body(carry, gp):
                return jax.lax.scan(inner, carry, gp)

            x, ys_g = jax.lax.scan(_remat(cfg, group_body), x, grouped)
            y_main = jax.tree.map(
                lambda a: a.reshape((cfg.num_layers,) + a.shape[2:]), ys_g)
        ys.append(y_main)
        if collect_kv:
            kv = jax.tree.map(lambda *a: jnp.concatenate(a, axis=0),
                              *[y["kv"] for y in ys])
        else:
            kv = None
        if y_main is not None and "aux" in y_main:
            aux_out = {k: jnp.mean(v) for k, v in y_main["aux"].items()
                       if k != "moe_held"}
            if stats:
                aux_out["moe_held"] = jnp.sum(y_main["aux"]["moe_held"],
                                              axis=0)
    x = nn.rms_norm(x, params["final_ln"], cfg.norm_eps)
    return x, kv, aux_out


def _cache_rows(cfg, ap, h, sin, cos):
    """One layer's decode-cache rows of the tokens whose normed input is h."""
    if cfg.kv_lora_rank:
        return nn.mla_latent(cfg, ap, h, sin, cos)
    _, k, v = nn.qkv_project(cfg, ap, h)
    k = nn.apply_rope(k, sin, cos)
    return {"k": _kv_rows(k), "v": _kv_rows(v)}


def _kv_rows(x):
    """(B,S,KV,hd) keys or values -> a dense cache's rows (B,S,KV*hd): one
    row per token, its KV heads side by side, as wide as a lane tile for
    narrow GQA (qwen2: 2 x 64), so that the cache keeps one layout at rest
    and in the decode loop."""
    return x.reshape(x.shape[:2] + (-1,))


def expert_counts(cfg, held, real):
    """Expert counters of a batch of tokens, summed over the MoE layers:
    `routes`, the (token, expert) routes of real tokens over every routed
    expert; `routes_held`, those that land on experts held here; `rows`, the
    held-expert rows the layers ran (every token, pads included). held:
    (B, S) routes held per token; real: (B, S) bool."""
    n_moe = cfg.num_layers - _dense_lead(cfg)
    return {"routes": jnp.sum(real, dtype=jnp.int32)
            * (cfg.experts_per_token * n_moe),
            "routes_held": jnp.sum(jnp.where(real, held, 0),
                                   dtype=jnp.int32),
            "rows": jnp.asarray(real.size * cfg.num_experts * n_moe,
                                jnp.int32)}


def remat_group_size(cfg) -> int:
    """Largest divisor of num_layers <= 8 (1 disables grouping)."""
    if cfg.remat == "none" or cfg.block_pattern:
        return 1
    for g in range(min(8, cfg.num_layers), 0, -1):
        if cfg.num_layers % g == 0:
            return g
    return 1


def dense_group_fwd(cfg, gp, x, sin, cos):
    """One remat group of G stacked dense layers (dry-run cost probe; the
    same inner-scan + inner-checkpoint structure as lm_hidden's group_body)."""
    def body(carry, lp):
        y, _ = _dense_layer_full(cfg, lp, carry, sin, cos, None)
        return y, None

    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    y, _ = jax.lax.scan(body, x, gp)
    return y


def _pattern_tail(cfg):
    pat = tuple(cfg.block_pattern)
    return pat[: cfg.num_layers - (cfg.num_layers // len(pat)) * len(pat)]


# ---------------------------------------------------------------------------
# Decode (single-token serve step) + cache
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, cache_dtype=jnp.bfloat16):
    """Abstract-safe cache init. Returns (cache, logical_axes)."""
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    if cfg.block_pattern:
        pat = tuple(cfg.block_pattern)
        G = cfg.num_layers // len(pat)
        tail = _pattern_tail(cfg)

        def group_cache(n, pattern):
            c, a = {}, {}
            for i, kind in enumerate(pattern):
                name = f"b{i}_{kind}"
                if kind == "attention":
                    W = cfg.window_size or max_len
                    T = min(W, max_len) if cfg.window_size else max_len
                    c[name] = {
                        "k": jnp.zeros((n, batch, T, KV, hd), cache_dtype),
                        "v": jnp.zeros((n, batch, T, KV, hd), cache_dtype)}
                    a[name] = {
                        "k": ("layers", "batch", None, "kv_heads", "head_dim"),
                        "v": ("layers", "batch", None, "kv_heads", "head_dim")}
                elif kind == "recurrent":
                    c[name] = {
                        "conv": jnp.zeros((n, batch, cfg.conv_width - 1, cfg.d_rnn), jnp.float32),
                        "h": jnp.zeros((n, batch, cfg.d_rnn), jnp.float32)}
                    a[name] = {"conv": ("layers", "batch", None, "rnn"),
                               "h": ("layers", "batch", "rnn")}
                elif kind == "mlstm":
                    H = cfg.num_heads
                    c[name] = {
                        "conv": jnp.zeros((n, batch, cfg.conv_width - 1, cfg.d_model), jnp.float32),
                        "C": jnp.zeros((n, batch, H, hd, hd), jnp.float32),
                        "n": jnp.zeros((n, batch, H, hd), jnp.float32),
                        "m": jnp.full((n, batch, H), -1e30, jnp.float32)}
                    a[name] = {"conv": ("layers", "batch", None, "inner"),
                               "C": ("layers", "batch", "heads", "head_dim", None),
                               "n": ("layers", "batch", "heads", "head_dim"),
                               "m": ("layers", "batch", "heads")}
                elif kind == "slstm":
                    D = cfg.d_model
                    c[name] = {
                        "conv": jnp.zeros((n, batch, cfg.conv_width - 1, D), jnp.float32),
                        "c": jnp.zeros((n, batch, D), jnp.float32),
                        "n2": jnp.zeros((n, batch, D), jnp.float32),
                        "h": jnp.zeros((n, batch, D), jnp.float32),
                        "m": jnp.full((n, batch, D), -1e30, jnp.float32)}
                    a[name] = {"conv": ("layers", "batch", None, "inner"),
                               "c": ("layers", "batch", "inner"),
                               "n2": ("layers", "batch", "inner"),
                               "h": ("layers", "batch", "inner"),
                               "m": ("layers", "batch", "inner")}
            return c, a

        cache, axes = {}, {}
        cache["groups"], axes["groups"] = group_cache(G, pat)
        if tail:
            cache["tail"], axes["tail"] = group_cache(1, tail)
        return cache, axes

    L = cfg.num_layers
    if cfg.kv_lora_rank:
        # MLA: the normed latent and the roped rope key, per token and layer
        cache = {"c_kv": jnp.zeros((L, batch, max_len, cfg.kv_lora_rank),
                                   cache_dtype),
                 "k_pe": jnp.zeros((L, batch, max_len, cfg.qk_rope_head_dim),
                                   cache_dtype)}
        axes = {"c_kv": ("layers", "batch", None, None),
                "k_pe": ("layers", "batch", None, None)}
        return cache, axes
    cache = {"k": jnp.zeros((L, batch, max_len, KV * hd), cache_dtype),
             "v": jnp.zeros((L, batch, max_len, KV * hd), cache_dtype)}
    axes = {"k": ("layers", "batch", None, "kv_heads"),
            "v": ("layers", "batch", None, "kv_heads")}
    return cache, axes


def _attn_decode(cfg, lp, x, c, sin, cos, pos, ctx, window: int = 0,
                 layer=None):
    """One attention block, single token, over one layer's cache c: {k, v}
    as rows (B,T,KV*hd) or per head (B,T,KV,hd), or under MLA the latent
    cache {c_kv, k_pe}, attended in the absorbed form. With `layer`, c is
    the layer-stacked cache (L,B,T,...): the block writes its token's rows
    into layer `layer` in place and attends that layer. pos: scalar, or (B,)
    one position per row. Returns (y, c_new)."""
    h = nn.rms_norm(x, lp["ln1"] if "ln1" in lp else lp["ln"], cfg.norm_eps)
    ap = lp["attn"] if "attn" in lp else lp["core"]
    if cfg.kv_lora_rank:
        q_nope, q_pe = nn.mla_query(cfg, ap, h, sin, cos)
        c = nn.latent_cache_update(c, nn.mla_latent(cfg, ap, h, sin, cos),
                                   pos, layer=layer)
        cl = nn.layer_view(c, layer)
        o = nn.mla_absorbed_attention(ap, q_nope, q_pe, cl["c_kv"],
                                      cl["k_pe"], pos, nn.attention_scale(cfg))
        return x + nn.out_project(cfg, ap, o), c
    q, k, v = nn.qkv_project(cfg, ap, h)
    q = nn.apply_rope(q, sin, cos)
    k = nn.apply_rope(k, sin, cos)
    # Decode layout must FOLLOW the cache layout (gathering a 32k-token KV
    # cache per step would dwarf the step itself). With GQA kv < TP the cache
    # shards head_dim over the model axis, so q/k/v take head_dim sharding
    # and the QK^T partial products all-reduce only (B,1,T)-sized logits.
    tp = ctx.tp_size() if ctx is not None else 1
    if k.shape[2] % tp == 0:
        q = _act(ctx, q, "batch", None, "heads", None)
        k = _act(ctx, k, "batch", None, "kv_heads", None)
        v = _act(ctx, v, "batch", None, "kv_heads", None)
    else:
        q = _act(ctx, q, "batch", None, None, "head_dim")
        k = _act(ctx, k, "batch", None, None, "head_dim")
        v = _act(ctx, v, "batch", None, None, "head_dim")
    rows = c["k"].ndim == 3 + (layer is not None)
    if rows:
        k, v = _kv_rows(k), _kv_rows(v)
    kc, vc = nn.cache_update(c["k"], c["v"], k, v, pos, window=window,
                             layer=layer)
    cl = nn.layer_view({"k": kc, "v": vc}, layer)
    attend = nn.decode_attention_rows if rows else nn.decode_attention
    o = attend(q, cl["k"], cl["v"], pos, window=window)
    o = _act(ctx, o, "batch", None, None, None)
    o = nn.out_project(cfg, ap, o)
    return x + _act(ctx, o, "batch", None, None), {"k": kc, "v": vc}


def lm_decode_step(cfg, params, cache, tokens, pos, ctx=None,
                   stats: bool = False, real=None):
    """One serve step. tokens: (B,) int32; pos: int32, the 0-based absolute
    position of this token, a scalar for every row or (B,) one per row.
    Returns (logits (B,V), new_cache), and with `stats` the expert counters
    of the step's tokens (`expert_counts`; `real` (B,) bool marks the rows
    whose routes count, default every row).

    A layer-stacked cache (the dense families: leaves (L,B,T,...)) is the
    layer loop's carry, and each layer writes one row per batch row into
    it in place; the `block_pattern` families carry per-group states."""
    x = nn.embed_tokens(cfg, params["embed"], tokens[:, None])   # (B,1,D)
    x = _act(ctx, x, "batch", None, None)
    sin, cos = _rope(cfg, jnp.reshape(pos, (-1, 1)) if jnp.ndim(pos)
                     else pos[None])
    held = None

    if cfg.block_pattern:
        pat = tuple(cfg.block_pattern)

        def make_gbody(pattern):
            def gbody(carry, sl):
                gp, gc = sl
                y = carry
                gc_new = {}
                for i, kind in enumerate(pattern):
                    name = f"b{i}_{kind}"
                    lp, c = gp[name], gc[name]
                    if kind == "attention":
                        y, gc_new[name] = _attn_decode(
                            cfg, {"ln": lp["ln"], "core": lp["core"]},
                            y, c, sin, cos, pos, ctx,
                            window=cfg.window_size)
                        if "mlp" in lp:
                            y, _ = _mlp_sub(cfg, lp, y, ctx)
                    elif kind == "recurrent":
                        h = nn.rms_norm(y, lp["ln"], cfg.norm_eps)
                        o, (cs, hs) = rec_lib.recurrent_block(
                            cfg, lp["core"], h,
                            conv_state=c["conv"], h_state=c["h"], decode=True)
                        y = y + o
                        gc_new[name] = {"conv": cs, "h": hs}
                        if "mlp" in lp:
                            y, _ = _mlp_sub(cfg, lp, y, ctx)
                    elif kind == "mlstm":
                        h = nn.rms_norm(y, lp["ln"], cfg.norm_eps)
                        o, (cs, cell) = xlstm_lib.mlstm_block(
                            cfg, lp["core"], h,
                            state=(c["conv"], (c["C"], c["n"], c["m"])),
                            decode=True)
                        y = y + o
                        gc_new[name] = {"conv": cs, "C": cell[0],
                                        "n": cell[1], "m": cell[2]}
                    elif kind == "slstm":
                        h = nn.rms_norm(y, lp["ln"], cfg.norm_eps)
                        o, (cs, cell) = xlstm_lib.slstm_block(
                            cfg, lp["core"], h,
                            state=(c["conv"], (c["c"], c["n2"], c["h"], c["m"])),
                            decode=True)
                        y = y + o
                        gc_new[name] = {"conv": cs, "c": cell[0], "n2": cell[1],
                                        "h": cell[2], "m": cell[3]}
                return y, gc_new
            return gbody

        x, groups_new = jax.lax.scan(make_gbody(pat), x,
                                     (params["groups"], cache["groups"]))
        cache_new = {"groups": groups_new}
        if "tail" in params:
            x, tail_new = jax.lax.scan(make_gbody(_pattern_tail(cfg)), x,
                                       (params["tail"], cache["tail"]))
            cache_new["tail"] = tail_new
    else:
        # The KV cache is a loop CARRY that each layer writes one row per
        # batch row into (single buffer), NOT a scan xs->ys pair — the
        # xs/ys form double-buffers the multi-GB cache (§Perf C8).
        # Layer li of the cache is dense layer li for li < Ld, else MoE
        # layer li - Ld.
        L, Ld = cfg.num_layers, _dense_lead(cfg)

        def body(carry, sl):
            y, cc = carry
            lp, li = sl
            y, cc = _attn_decode(cfg, lp, y, cc, sin, cos, pos, ctx,
                                 layer=li)
            y, aux = _mlp_sub(cfg, lp, y, ctx)
            return (y, cc), (aux.get("moe_held") if stats else None)

        carry = (x, cache)
        if "dense_layers" in params:
            carry, _ = jax.lax.scan(body, carry, (params["dense_layers"],
                                                  jnp.arange(Ld)))
        (x, cache_new), held = jax.lax.scan(
            body, carry, (params["layers"], jnp.arange(Ld, L)))

    x = nn.rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = nn.logits_from_hidden(cfg, params["embed"], x)[:, 0, :]
    logits = _act(ctx, logits, "batch", "vocab")
    if stats:
        B = tokens.shape[0]
        held = (jnp.zeros((B, 1), jnp.int32) if held is None
                else jnp.sum(held, axis=0))
        real = (jnp.ones((B, 1), jnp.bool_) if real is None
                else jnp.reshape(real, (B, 1)))
        return logits, cache_new, expert_counts(cfg, held, real)
    return logits, cache_new


def _hybrid_group_prefill(cfg, gp, x, sin, cos, ctx, pattern, cache_dtype):
    """One pattern group over the full prompt, returning decode states."""
    states = {}
    W = cfg.window_size
    for i, kind in enumerate(pattern):
        name = f"b{i}_{kind}"
        lp = gp[name]
        if kind == "attention":
            h = nn.rms_norm(x, lp["ln"], cfg.norm_eps)
            q, k, v = nn.qkv_project(cfg, lp["core"], h)
            q = nn.apply_rope(q, sin, cos)
            k = nn.apply_rope(k, sin, cos)
            o = _attention_dispatch(cfg, q, k, v, W)
            x = x + nn.out_project(cfg, lp["core"], o)
            if "mlp" in lp:
                x, _ = _mlp_sub(cfg, lp, x, ctx)
            S = k.shape[1]
            T = min(W or S, S)
            # ring alignment holds when S % W == 0 (all assigned shapes)
            states[name] = {"k": k[:, -T:].astype(cache_dtype),
                            "v": v[:, -T:].astype(cache_dtype)}
        elif kind == "recurrent":
            h = nn.rms_norm(x, lp["ln"], cfg.norm_eps)
            o, (cs, hs) = rec_lib.recurrent_block(cfg, lp["core"], h)
            x = x + o
            states[name] = {"conv": cs.astype(jnp.float32), "h": hs}
            if "mlp" in lp:
                x, _ = _mlp_sub(cfg, lp, x, ctx)
        elif kind == "mlstm":
            h = nn.rms_norm(x, lp["ln"], cfg.norm_eps)
            o, (cs, cell) = xlstm_lib.mlstm_block(cfg, lp["core"], h)
            x = x + o
            states[name] = {"conv": cs.astype(jnp.float32), "C": cell[0],
                            "n": cell[1], "m": cell[2]}
        elif kind == "slstm":
            h = nn.rms_norm(x, lp["ln"], cfg.norm_eps)
            o, (cs, cell) = xlstm_lib.slstm_block(cfg, lp["core"], h)
            x = x + o
            states[name] = {"conv": cs.astype(jnp.float32), "c": cell[0],
                            "n2": cell[1], "h": cell[2], "m": cell[3]}
    return x, states


def lm_prefill(cfg, params, tokens, max_len: int, ctx=None,
               frontend_embeds=None, cache_dtype=jnp.bfloat16,
               lengths=None, stats: bool = False):
    """Prefill: run the trunk over the prompt and build the decode cache.
    Returns (last_logits (B,V), cache), and with `stats` the expert counters
    of the prompts (`expert_counts`; positions past `lengths` are pads).

    `lengths` (B,) enables RIGHT-PADDED prompts (runtime/prefill.py bucket
    padding): the last-hidden gather happens at each row's true final
    position instead of S-1. Only the dense/window-free family supports it —
    causal attention means real positions never attend pad columns, and the
    decode-time mask (`slots <= pos` with pos starting at the true length)
    keeps the pad garbage written beyond `lengths` in the KV cache forever
    unobservable: decode overwrites slot `pos` BEFORE attending it. Stateful
    families (recurrent/ssm/xlstm scans fold every position into their
    state) and ring-buffer window caches cannot skip padding, so `lengths`
    raises there rather than silently corrupting."""
    B = tokens.shape[0]
    if cfg.block_pattern:
        if lengths is not None:
            raise NotImplementedError(
                "length-gathered (right-padded) prefill needs positions to "
                "be skippable; recurrent/ssm/window states fold every "
                "position in — pad-to-bucket is dense-family only")
        x = nn.embed_tokens(cfg, params["embed"], tokens)
        x = _act(ctx, x, "batch", "seq", None)
        S = x.shape[1]
        sin, cos = _rope(cfg, jnp.arange(S))
        pat = tuple(cfg.block_pattern)

        def make_gbody(pattern):
            def gbody(carry, gp):
                return _hybrid_group_prefill(cfg, gp, carry, sin, cos, ctx,
                                             pattern, cache_dtype)
            return gbody

        x, groups_state = jax.lax.scan(make_gbody(pat), x, params["groups"])
        cache = {"groups": groups_state}
        if "tail" in params:
            x, tail_state = jax.lax.scan(make_gbody(_pattern_tail(cfg)), x,
                                         params["tail"])
            cache["tail"] = tail_state
        x = nn.rms_norm(x, params["final_ln"], cfg.norm_eps)
        logits = nn.logits_from_hidden(cfg, params["embed"], x[:, -1:, :])[:, 0, :]
        return logits, cache

    if lengths is not None and cfg.window_size:
        raise NotImplementedError(
            "length-gathered prefill is incompatible with ring-buffer "
            "window caches: pad entries would wrap onto real slots")
    h, kv, aux = lm_hidden(cfg, params, tokens, ctx, frontend_embeds,
                           collect_kv=True, stats=stats)
    cache, _ = init_cache(cfg, B, max_len, cache_dtype)
    # kv leaves (L, B, S, ...) fill the first S positions of the cache
    cache = {n: jax.lax.dynamic_update_slice(
        a, kv[n].astype(cache_dtype), (0,) * a.ndim) for n, a in cache.items()}
    if lengths is None:
        h_last = h[:, -1:, :]
    else:
        P = frontend_embeds.shape[1] if frontend_embeds is not None else 0
        idx = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1 + P, 0,
                       h.shape[1] - 1)
        h_last = jnp.take_along_axis(h, idx[:, None, None], axis=1)
    logits = nn.logits_from_hidden(cfg, params["embed"], h_last)[:, 0, :]
    if stats:
        S = tokens.shape[1]
        real = (jnp.ones((B, S), jnp.bool_) if lengths is None else
                jnp.arange(S)[None, :] < jnp.asarray(lengths)[:, None])
        held = aux.get("moe_held", jnp.zeros((B, S), jnp.int32))
        return logits, cache, expert_counts(cfg, held, real)
    return logits, cache


def lm_loss(cfg, params, batch, ctx=None):
    """batch: {"tokens": (B,S), "targets": (B,S), ["frontend_embeds"]}.

    Loss over text positions only (frontend positions excluded). Long
    sequences stream the head+CE over seq chunks so (B,S,V) logits never
    materialize."""
    fe = batch.get("frontend_embeds")
    h, _, aux = lm_hidden(cfg, params, batch["tokens"], ctx, fe)
    if fe is not None:
        h = h[:, fe.shape[1]:, :]     # text positions only
    if h.shape[1] > nn.CE_CHUNK:
        # gather the (bf16) hidden over seq ONCE before the CE scan — the
        # scan slices seq, and slicing a seq-sharded tensor reshards per step
        h = _act(ctx, h, "batch", None, None)
        loss = nn.chunked_cross_entropy(cfg, params["embed"], h,
                                        batch["targets"])
    else:
        logits = nn.logits_from_hidden(cfg, params["embed"], h)
        logits = _act(ctx, logits, "batch", "seq", "vocab")
        loss = nn.cross_entropy_loss(logits, batch["targets"])
    metrics = {"loss": loss}
    for k, v in aux.items():
        metrics[k] = v
    if "moe_aux" in aux:
        loss = loss + 0.01 * aux["moe_aux"]
    return loss, metrics
