"""Mixture-of-Experts layer: softmax router, top-k, and the experts held here.

Two paths:
  * one device (and a mesh without a model axis): DROPLESS. The router
    keeps its full width (`router_experts`, every expert of every chip of
    an expert-parallel deployment); this layer holds `num_experts` of them,
    starting at router index `expert_offset`, and computes the part of the
    result its held experts give: dense over the held experts, each
    expert's rows weighted by the token's gate for it (zero for a token
    not routed there). No token is dropped, so right-padded pack rows
    cannot take a real token's place.
  * expert parallel on a mesh (`moe_mlp_ep`): capacity-based dispatch with
    an all_to_all exchange over the model axis; tokens over capacity are
    dropped (standard "token dropping"). It holds every expert of the
    router across the mesh.

Shared experts (`shared_d_ff`) are one SwiGLU MLP every token passes
through, added to the routed part. Top-k weights are renormalised unless
`moe_raw_topk`. Determinism: the router's logits are float32 products of
the bf16 hidden state at HIGHEST precision and top-k on identical inputs is
bitwise deterministic, so SEDAR replicas route identically (DESIGN.md §4).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import init_mlp, mlp, normal_init


def router_width(cfg) -> int:
    return cfg.router_experts or cfg.num_experts


def init_moe(key, cfg, layers: Optional[int] = None):
    D, E = cfg.d_model, cfg.num_experts
    F = cfg.moe_d_ff or cfg.d_ff
    L = (layers,) if layers else ()
    lax_pref = ("layers",) if layers else ()
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p = {
        "router": normal_init(ks[0], L + (D, router_width(cfg)), pdt,
                              1.0 / math.sqrt(D)),
        "w_gate": normal_init(ks[1], L + (E, D, F), pdt, 1.0 / math.sqrt(D)),
        "w_up":   normal_init(ks[2], L + (E, D, F), pdt, 1.0 / math.sqrt(D)),
        "w_down": normal_init(ks[3], L + (E, F, D), pdt, 1.0 / math.sqrt(F)),
    }
    ax = {
        "router": lax_pref + ("embed", None),
        "w_gate": lax_pref + ("experts", "embed", "mlp"),
        "w_up":   lax_pref + ("experts", "embed", "mlp"),
        "w_down": lax_pref + ("experts", "mlp", "embed"),
    }
    if cfg.shared_d_ff:
        p["shared"], ax["shared"] = init_mlp(ks[4], cfg, layers=layers,
                                             d_ff=cfg.shared_d_ff)
    return p, ax


def route(cfg, router, xt):
    """Softmax router over its full width, float32 logits of the compute-
    dtype hidden state, greedy top-k. xt: (T, D). Returns (probs (T, E_r),
    gate weights (T, k) f32, expert indices (T, k))."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, cfg.experts_per_token)
    if not cfg.moe_raw_topk:
        gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)
    return probs, gate_w, gate_idx


def balance_loss(probs, gate_idx, n_experts: int):
    """Switch-style load-balance loss over the router's full width."""
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(gate_idx, n_experts,
                                         dtype=jnp.float32), axis=1), axis=0)
    return n_experts * jnp.sum(me * ce)


def held_experts(cfg, p, xt, gate_w, gate_idx):
    """The held experts' part of the routed result, dropless. xt: (T, D).
    Every held expert runs on every token (T x num_experts rows); a row's
    output is weighted by the token's gate for that expert, zero where the
    token was not routed there. Returns (out (T, D), routes held per token
    (T,) int32)."""
    E = cfg.num_experts
    dt = xt.dtype
    local = gate_idx - cfg.expert_offset                      # (T, k)
    here = (local >= 0) & (local < E)
    gates = jnp.sum(jnp.where(here[..., None],
                              jax.nn.one_hot(local, E, dtype=jnp.float32)
                              * gate_w[..., None], 0.0), axis=1)   # (T, E)
    h_g = jnp.einsum("td,edf->etf", xt, p["w_gate"].astype(dt))
    h_u = jnp.einsum("td,edf->etf", xt, p["w_up"].astype(dt))
    h = jax.nn.silu(h_g.astype(jnp.float32)) * h_u.astype(jnp.float32)
    h = (h * gates.T[:, :, None]).astype(dt)
    out = jnp.einsum("etf,efd->td", h, p["w_down"].astype(dt))
    return out, jnp.sum(here, axis=-1, dtype=jnp.int32)


def moe_mlp_ep(cfg, p, x, *, capacity_factor: float = 1.25, ctx=None):
    """Expert-parallel MoE via shard_map + all_to_all (the production path).

    Tokens are sharded over every mesh axis (data x model); each device
    routes ITS tokens locally (local cumsum positions, local capacity, local
    scatter — kilobyte-scale buffers), then one all_to_all over the model
    axis moves token slices to their expert's owner, the expert FFN runs on
    local weights, and the reverse all_to_all brings results home. GSPMD
    cannot infer this from a global scatter (it replicates the dispatch
    buffers — tens of GB at 1M tokens); shard_map makes the exchange
    explicit. Used whenever a mesh ctx is present and E % TP == 0."""
    import numpy as _np
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    dt = x.dtype
    rules = ctx.resolver.rules
    mesh = ctx.mesh
    token_axes = tuple(rules.data_axes) + tuple(rules.model_axes)
    n_tok_shards = rules.axis_size(mesh, token_axes)
    tp = rules.axis_size(mesh, rules.model_axes)
    model_axis = rules.model_axes[0]
    Tl = T // n_tok_shards
    Cl = max(int(math.ceil(k * Tl / E * capacity_factor)), 4)
    E_l = E // tp

    def body(xt, router, wg, wu, wd):
        # xt: (Tl, D) local tokens; router: (D, E); w*: (E_l, D, F) local
        logits = jnp.einsum("td,de->te", xt, router.astype(dt)
                            ).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_idx = jax.lax.top_k(probs, k)
        if not cfg.moe_raw_topk:
            gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)
        aux = jax.lax.pmean(balance_loss(probs, gate_idx, E), token_axes)

        flat_e = gate_idx.reshape(Tl * k)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                                  flat_e[:, None], axis=1)[:, 0]
        keep = pos < Cl
        e_idx = jnp.where(keep, flat_e, 0)
        c_idx = jnp.where(keep, pos, Cl - 1)
        src = jnp.repeat(xt, k, axis=0) if k > 1 else xt
        contrib = jnp.where(keep[:, None], src, 0).astype(dt)
        buf = jnp.zeros((E, Cl, D), dt).at[e_idx, c_idx].add(
            contrib, mode="drop")                     # local dispatch

        # token -> expert exchange: each peer gets its experts' queues
        # (tiled all_to_all: (E, Cl, D) -> (E/tp, tp*Cl, D); its transpose is
        # the symmetric reverse exchange, which keeps the VJP well-formed)
        recv = jax.lax.all_to_all(buf, model_axis, split_axis=0,
                                  concat_axis=1, tiled=True)  # (E_l, tp*Cl, D)

        hg = jnp.einsum("ecd,edf->ecf", recv, wg.astype(dt))
        hu = jnp.einsum("ecd,edf->ecf", recv, wu.astype(dt))
        h = jax.nn.silu(hg.astype(jnp.float32)).astype(dt) * hu
        outb = jnp.einsum("ecf,efd->ecd", h, wd.astype(dt))  # (E_l, tp*Cl, D)

        # reverse exchange: results back to the token owners
        back = jax.lax.all_to_all(outb, model_axis, split_axis=1,
                                  concat_axis=0, tiled=True)  # (E, Cl, D)

        gathered = back[e_idx, c_idx]
        gathered = jnp.where(keep[:, None], gathered, 0)
        w = gate_w.reshape(Tl * k).astype(jnp.float32)
        out = (gathered.astype(jnp.float32) * w[:, None]) \
            .reshape(Tl, k, D).sum(axis=1).astype(dt)
        drop = 1.0 - jnp.mean(keep.astype(jnp.float32))
        return out, aux, jax.lax.pmean(drop, token_axes)

    tok_spec = P(token_axes if len(token_axes) > 1 else token_axes[0], None)
    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(tok_spec, P(), P(model_axis, None, None),
                                 P(model_axis, None, None),
                                 P(model_axis, None, None)),
                       out_specs=(tok_spec, P(), P()), check_vma=False)
    out, aux, drop = sm(x.reshape(T, D), p["router"], p["w_gate"],
                        p["w_up"], p["w_down"])
    return out.reshape(B, S, D), {"moe_aux": aux, "moe_drop_frac": drop}


def moe_mlp(cfg, p, x, *, capacity_factor: float = 1.25, ctx=None):
    """x: (B, S, D) -> (B, S, D), plus an aux dict: the load-balance loss
    `moe_aux` and, on the dropless path, `moe_held` (B, S) int32, the
    routes of each token that land on experts held here (the expert
    counters of `runtime/serve.py` read it).

    The expert-parallel path runs when a mesh with a model axis holds every
    expert of the router; otherwise the held experts run dropless here."""
    B, S, D = x.shape
    shared = (mlp(cfg, p["shared"], x) if "shared" in p else None)
    if ctx is not None and router_width(cfg) == cfg.num_experts:
        r = ctx.resolver.rules
        tp = r.axis_size(ctx.mesh, r.model_axes)
        nsh = r.axis_size(ctx.mesh, tuple(r.data_axes) + tuple(r.model_axes))
        if cfg.num_experts % tp == 0 and (B * S) % nsh == 0 and tp > 1:
            out, aux = moe_mlp_ep(cfg, p, x, capacity_factor=capacity_factor,
                                  ctx=ctx)
            return (out if shared is None else out + shared), aux

    xt = x.reshape(B * S, D)
    if ctx is not None:
        xt = ctx.act(xt, "batch", None)
    probs, gate_w, gate_idx = route(cfg, p["router"], xt)
    out, held = held_experts(cfg, p, xt, gate_w, gate_idx)
    out = out.reshape(B, S, D)
    if shared is not None:
        out = out + shared
    return out, {"moe_aux": balance_loss(probs, gate_idx, router_width(cfg)),
                 "moe_held": held.reshape(B, S)}
