"""Mixture-of-Experts layer: top-k routing with capacity-based dispatch.

Design (TPU-native, HLO-FLOPs-honest):
  * router: dense (D, E) matmul + top-k.
  * dispatch: tokens are scattered into per-expert buffers (E, C, D) where
    C = capacity = ceil(k * T / E) * capacity_factor. Scatter/gather are
    memory ops, NOT one-hot matmuls, so HLO FLOPs reflect only the *active*
    expert compute (2*k*T*D*F-ish) — keeping MODEL_FLOPS/HLO_FLOPs meaningful.
  * expert compute: batched einsum over the expert axis; experts shard over
    the "model" mesh axis (expert parallelism). GSPMD inserts the
    dispatch/combine collectives (all-to-all / all-gather depending on the
    token sharding) — these show up in the collective roofline term.
  * determinism: top-k on identical inputs is bitwise deterministic, so SEDAR
    replicas stay in lockstep (DESIGN.md §4); no routing jitter under SEDAR.

Dropped tokens (over capacity) fall back to the residual path (standard
"token dropping" semantics, loss-free at the framework level).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import normal_init


def init_moe(key, cfg, layers: Optional[int] = None):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    L = (layers,) if layers else ()
    lax_pref = ("layers",) if layers else ()
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {
        "router": normal_init(ks[0], L + (D, E), pdt, 1.0 / math.sqrt(D)),
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
    return p, ax


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
        gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32),
                              axis=1), axis=0)
        aux = E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, token_axes)

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
    """x: (B, S, D) -> (B, S, D), plus aux losses dict.

    Group-local dispatch: tokens are viewed as (G, T/G, ...) with G = the
    data-parallel degree, the leading dim pinned to the data axis. Routing
    positions (cumsum) and the dispatch scatter are then LOCAL per data
    shard — per-group capacity, the standard EP formulation — and the only
    cross-device movement is the intended token->expert exchange over the
    model axis (all-to-all in the compiled HLO). Without the grouping GSPMD
    must treat the scatter as global and falls back to replicating the
    (E, C, D) buffers, which at 1M tokens is tens of GB per device.
    """
    def act(t, *logical):
        return ctx.act(t, *logical) if ctx is not None else t

    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    dt = x.dtype

    # production path: explicit expert parallelism when a mesh is present
    if ctx is not None:
        r = ctx.resolver.rules
        tp = r.axis_size(ctx.mesh, r.model_axes)
        nsh = r.axis_size(ctx.mesh, tuple(r.data_axes) + tuple(r.model_axes))
        if E % tp == 0 and T % nsh == 0 and tp > 1:
            return moe_mlp_ep(cfg, p, x, capacity_factor=capacity_factor,
                              ctx=ctx)

    # dispatch group count = data-parallel degree (1 when mesh-free)
    G = 1
    if ctx is not None:
        r = ctx.resolver.rules
        G = r.axis_size(ctx.mesh, r.data_axes)
        if T % G != 0:
            G = 1
    Tg = T // G

    xt = act(x.reshape(T, D), "batch", None)

    # ---- route ---------------------------------------------------------------
    logits = jnp.einsum("td,de->te", xt, p["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    gate_w, gate_idx = jax.lax.top_k(probs, k)                  # (T, k)
    gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)   # renormalize

    # load-balance aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)                                # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32), axis=1), axis=0)
    aux_loss = E * jnp.sum(me * ce)

    # ---- group-local dispatch ---------------------------------------------------
    Cg = int(math.ceil(k * Tg / E * capacity_factor))
    Cg = max(Cg, 4)
    flat_e = gate_idx.reshape(G, Tg * k)                         # (G, Tkg)
    flat_e = act(flat_e, "batch", None)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # (G, Tkg, E)
    pos_in_e = jnp.cumsum(onehot, axis=1) - onehot               # per-group cumsum
    pos = jnp.take_along_axis(pos_in_e, flat_e[..., None],
                              axis=2)[..., 0]                    # (G, Tkg)
    keep = pos < Cg

    src = (jnp.repeat(xt, k, axis=0) if k > 1 else xt).reshape(G, Tg * k, D)
    src = act(src, "batch", None, None)
    gi = jnp.broadcast_to(jnp.arange(G)[:, None], flat_e.shape)  # (G, Tkg)
    e_idx = jnp.where(keep, flat_e, 0)
    c_idx = jnp.where(keep, pos, Cg - 1)
    contrib = jnp.where(keep[..., None], src, 0).astype(dt)

    buf = jnp.zeros((G, E, Cg, D), dt)
    buf = buf.at[gi, e_idx, c_idx].add(contrib, mode="drop")
    buf = act(buf, "batch", "experts", None, None)   # G->data, E->model (EP)

    # ---- expert compute --------------------------------------------------------
    h_g = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"].astype(dt))
    h_u = jnp.einsum("gecd,edf->gecf", buf, p["w_up"].astype(dt))
    h = jax.nn.silu(h_g.astype(jnp.float32)).astype(dt) * h_u
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(dt))
    out_buf = act(out_buf, "batch", "experts", None, None)

    # ---- combine ----------------------------------------------------------------
    gathered = out_buf[gi, e_idx, c_idx]                         # (G, Tkg, D)
    gathered = jnp.where(keep[..., None], gathered, 0)
    w = gate_w.reshape(G, Tg * k).astype(jnp.float32)
    out = (gathered.astype(jnp.float32) * w[..., None]) \
        .reshape(G, Tg, k, D).sum(axis=2)
    out = out.reshape(B, S, D).astype(dt)
    return out, {"moe_aux": aux_loss,
                 "moe_drop_frac": 1.0 - jnp.mean(keep.astype(jnp.float32))}
