"""Model facade: build_model(cfg) -> uniform API over every assigned family.

API:
    model.init(key)                       -> params (real arrays)
    model.abstract_params()               -> (ShapeDtypeStruct pytree, logical-axes pytree)
    model.loss(params, batch, ctx)        -> (loss, metrics)
    model.prefill(params, batch, max_len, ctx) -> (logits, cache)
    model.decode_step(params, cache, tokens, pos, ctx) -> (logits, cache)
                                            (both take `stats=True` on the
                                             decoder-only families and then
                                             add the expert counters)
    model.init_cache(batch, max_len)      -> (cache, logical-axes)
    model.probes(shape)                   -> scan-cost-correction probes (see
                                             DESIGN.md §7 / launch/dryrun.py)

Probes: XLA's cost_analysis counts each lax.scan body ONCE. Every model
therefore describes its scan structure as a list of Probe(name, fn,
arg_specs, multiplier): total_cost = cost(full_program)
+ sum_i multiplier_i * cost(probe_i). Probe functions are the *same* code
objects used inside the scans, so the correction is exact.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import encdec as encdec_lib
from repro.models import layers as nn
from repro.models import transformer as tfm


# ---------------------------------------------------------------------------
# Parameter counting (analytic; mirrors the init functions exactly)
# ---------------------------------------------------------------------------

def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    D, H, KV, hd, F, V = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                          cfg.head_dim, cfg.d_ff, cfg.vocab_size)

    def attn():
        if cfg.kv_lora_rank:
            R, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
            return (D * H * (dn + dr) + D * (R + dr) + R
                    + R * H * (dn + dv) + H * dv * D)
        n = D * H * hd + 2 * D * KV * hd + H * hd * D
        if cfg.qkv_bias:
            n += H * hd + 2 * KV * hd
        return n

    def mlp(width=F):
        if cfg.mlp_act == "swiglu":
            return 3 * D * width
        return 2 * D * width + width + D

    def moe():
        E = cfg.num_experts
        k = cfg.experts_per_token
        per_expert = 3 * D * (cfg.moe_d_ff or F)
        router = D * (cfg.router_experts or E)
        shared = mlp(cfg.shared_d_ff) if cfg.shared_d_ff else 0
        if active_only:
            return router + shared + k * per_expert
        return router + shared + E * per_expert

    def recurrent():
        R, W = cfg.d_rnn, cfg.conv_width
        return (2 * D * R + R * D + W * R + R          # branches + conv
                + 2 * (R * R + R) + R)                  # gates + Lambda

    def mlstm():
        return (D * 2 * D + cfg.conv_width * D + D      # up + conv
                + 3 * D * H * hd + 2 * (D * H + H)      # qkv + gates
                + D + D * D)                            # gn + down

    def slstm():
        Fp = int(cfg.proj_factor * D)
        return (cfg.conv_width * D + D                  # conv
                + 4 * (D * D + D) + 4 * H * hd * hd     # gates + recurrent
                + D + 3 * D * Fp)                       # gn + ffn (w_downf: Fp*D)

    total = V * D + D                                    # embed + final_ln
    if not cfg.tie_embeddings:
        total += D * V

    if cfg.family == "audio":
        total -= D   # enc-dec has per-stack final_lns, no global one
        layer = attn() + mlp() + 2 * D
        xlayer = attn() + D
        total += cfg.encoder_layers * layer + D
        total += cfg.num_layers * (layer + xlayer) + D
        return total

    if cfg.block_pattern:
        per_kind = {"attention": attn() + D, "recurrent": recurrent() + D,
                    "mlstm": mlstm() + D, "slstm": slstm() + D}
        if cfg.d_ff:
            per_kind["attention"] += mlp() + D
            per_kind["recurrent"] += mlp() + D
        pat = tuple(cfg.block_pattern)
        G = cfg.num_layers // len(pat)
        counts = list(pat) * G + list(pat[:cfg.num_layers - G * len(pat)])
        total += sum(per_kind[k] for k in counts)
        return total

    per_layer = attn() + 2 * D
    per_layer += moe() if (cfg.family == "moe" and cfg.num_experts) else mlp()
    Ld = cfg.first_dense_layers if cfg.family == "moe" else 0
    total += (cfg.num_layers - Ld) * per_layer
    total += Ld * (attn() + 2 * D + mlp(cfg.dense_d_ff or F))
    return total


# ---------------------------------------------------------------------------
# Probe descriptors
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Probe:
    name: str
    fn: Callable                 # positional args matching arg_specs
    arg_specs: Tuple[Any, ...]   # pytrees of ShapeDtypeStruct
    arg_axes: Tuple[Any, ...]    # matching pytrees of logical-axis tuples
    multiplier: float            # cost weight added on top of the full program


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def _slice_axes(axes_tree):
    """Drop the leading 'layers' entry from every axes tuple (stack -> slice)."""
    def f(t):
        if isinstance(t, tuple) and len(t) and t[0] == "layers":
            return t[1:]
        return t
    return jax.tree.map(f, axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and
                        all(isinstance(e, (str, type(None))) for e in x))


def _slice_specs(spec_tree):
    """Drop the leading stack dim from every ShapeDtypeStruct."""
    return jax.tree.map(lambda s: _sds(s.shape[1:], s.dtype), spec_tree)


def _grad_probe(fn, remat: bool = False):
    """fwd+bwd probe: cost of value_and_grad of sum(fn(...)) wrt the FLOAT
    args (integer args — positions, indices — are closed over). remat=True
    wraps fn in the same nothing_saveable checkpoint the real scan bodies
    use, so the probe's bwd includes the recompute."""
    if remat:
        fn = jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    def probe(*args):
        leaves, treedef = jax.tree_util.tree_flatten(args)
        is_float = [jnp.issubdtype(l.dtype, jnp.floating) for l in leaves]
        floats = [l for l, m in zip(leaves, is_float) if m]

        def scalar(fl):
            it = iter(fl)
            full = [next(it) if m else l for l, m in zip(leaves, is_float)]
            out = fn(*jax.tree_util.tree_unflatten(treedef, full))
            outs = [l for l in jax.tree.leaves(out)
                    if hasattr(l, "dtype") and jnp.issubdtype(l.dtype, jnp.floating)]
            return sum(jnp.sum(l.astype(jnp.float32)) for l in outs)

        return jax.value_and_grad(scalar)(floats)
    return probe


# ---------------------------------------------------------------------------
# Model facade
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    _init: Callable
    _loss: Callable
    _prefill: Callable
    _decode: Callable
    _init_cache: Callable
    _probes: Callable

    def init(self, key):
        return self._init(key)[0]

    def abstract_params(self):
        holder = {}

        def f(k):
            p, ax = self._init(k)
            holder["ax"] = ax
            return p

        shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
        return shapes, holder["ax"]

    def loss(self, params, batch, ctx=None):
        return self._loss(params, batch, ctx)

    def prefill(self, params, batch, max_len, ctx=None, **kw):
        return self._prefill(params, batch, max_len, ctx, **kw)

    def decode_step(self, params, cache, tokens, pos, ctx=None, **kw):
        return self._decode(params, cache, tokens, pos, ctx, **kw)

    @property
    def counts_experts(self) -> bool:
        """Whether prefill and decode_step give expert counters."""
        return self.cfg.family == "moe" and self.cfg.num_experts > 0

    def init_cache(self, batch, max_len, cache_dtype=jnp.bfloat16):
        return self._init_cache(batch, max_len, cache_dtype)

    def probes(self, shape: ShapeSpec) -> List[Probe]:
        return self._probes(shape)

    def param_count(self) -> int:
        return count_params_analytic(self.cfg)


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "audio":
        return _build_encdec(cfg)
    return _build_lm(cfg)


# -- decoder-only families ----------------------------------------------------

def _build_lm(cfg: ModelConfig) -> Model:
    def init(key):
        return tfm.init_lm(key, cfg)

    def loss(params, batch, ctx):
        return tfm.lm_loss(cfg, params, batch, ctx)

    def prefill(params, batch, max_len, ctx, **kw):
        return tfm.lm_prefill(cfg, params, batch["tokens"], max_len, ctx,
                              batch.get("frontend_embeds"),
                              lengths=batch.get("lengths"), **kw)

    def decode(params, cache, tokens, pos, ctx, **kw):
        return tfm.lm_decode_step(cfg, params, cache, tokens, pos, ctx, **kw)

    def init_cache(batch, max_len, cache_dtype):
        return tfm.init_cache(cfg, batch, max_len, cache_dtype)

    def probes(shape: ShapeSpec) -> List[Probe]:
        return _lm_probes(cfg, shape)

    return Model(cfg, init, loss, prefill, decode, init_cache, probes)


def _lm_probes(cfg: ModelConfig, shape: ShapeSpec) -> List[Probe]:
    """Scan-body probes for the decoder-only families."""
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    D = cfg.d_model
    model = build_model(cfg)
    pshapes, paxes = model.abstract_params()
    kind = shape.kind
    if kind in ("train", "prefill"):
        # the real programs run on bf16 weights (train pre-casts the f32
        # masters before the FSDP gathers; serving deploys bf16)
        pshapes = jax.tree.map(lambda t: _sds(t.shape, jnp.bfloat16), pshapes)
    out: List[Probe] = []

    if kind in ("train", "prefill"):
        P = cfg.frontend_seq if cfg.frontend else 0
        Sfull = S + P if cfg.family == "vlm" else S
        x_spec = _sds((B, Sfull, D), dt)
        x_axes = ("batch", "seq", None)
        sin_spec = _sds((Sfull, cfg.head_dim // 2), jnp.float32)

        if cfg.block_pattern:
            pat = tuple(cfg.block_pattern)
            G = cfg.num_layers // len(pat)
            gspecs = _slice_specs(jax.tree.map(
                lambda s: s, pshapes["groups"]))
            gaxes = _slice_axes(paxes["groups"])

            def group_fwd(gp, x, sin, cos):
                return tfm._hybrid_group_full(cfg, gp, x, sin, cos, None, pat)[0]

            fn = _grad_probe(group_fwd) if kind == "train" else group_fwd
            out.append(Probe("group", fn,
                             (gspecs, x_spec, sin_spec, sin_spec),
                             (gaxes, x_axes, (None, None), (None, None)),
                             multiplier=G - 1))

            # inner scan probes (xlstm): chunk body + token body
            chunk = min(cfg.mlstm_chunk, Sfull)
            NC = max(Sfull // chunk, 1)
            H, hd = cfg.num_heads, cfg.head_dim
            n_mlstm = sum(1 for k in pat if k == "mlstm")
            n_slstm = sum(1 for k in pat if k == "slstm")
            if n_mlstm and NC > 1:
                carry = ((_sds((B, H, hd, hd), jnp.float32),
                          _sds((B, H, hd), jnp.float32),
                          _sds((B, H), jnp.float32)))
                xs = (_sds((B, H, chunk, hd), dt), _sds((B, H, chunk, hd), dt),
                      _sds((B, H, chunk, hd), dt), _sds((B, H, chunk), jnp.float32),
                      _sds((B, H, chunk), jnp.float32))
                from repro.models.xlstm import mlstm_chunk_body
                fn = (_grad_probe(mlstm_chunk_body, remat=True)
                      if kind == "train" else mlstm_chunk_body)
                ca = (("batch", "heads", "head_dim", None),
                      ("batch", "heads", "head_dim"), ("batch", "heads"))
                xa = (("batch", "heads", None, "head_dim"),) * 3 + \
                     (("batch", "heads", None),) * 2
                out.append(Probe("mlstm_chunk", fn, (carry, xs), (ca, xa),
                                 multiplier=n_mlstm * G * (NC - 1)))
            if n_slstm and Sfull > 1:
                from repro.models.xlstm import slstm_token_body
                r = tuple(_sds((H, hd, hd), jnp.float32) for _ in range(4))
                carry = tuple(_sds((B, D), jnp.float32) for _ in range(4))
                xs = tuple(_sds((B, D), jnp.float32) for _ in range(4))

                def tok(r_mats, c, x):
                    return slstm_token_body(r_mats, (H, hd), c, x)

                fn = (_grad_probe(tok, remat=True) if kind == "train"
                      else tok)
                ra = tuple(("heads", "head_dim", None) for _ in range(4))
                ba = tuple(("batch", "inner") for _ in range(4))
                out.append(Probe("slstm_token", fn, (r, carry, xs),
                                 (ra, ba, ba),
                                 multiplier=n_slstm * G * (Sfull - 1)))
        else:
            lspecs = _slice_specs(pshapes["layers"])
            laxes = _slice_axes(paxes["layers"])

            def layer_fwd(lp, x, sin, cos):
                return tfm._dense_layer_full(cfg, lp, x, sin, cos, None)[0]

            G = tfm.remat_group_size(cfg)
            if kind == "train" and G > 1:
                # scan-of-scans remat: full program counts one group (which
                # itself counts one layer); corrections per DESIGN.md §7:
                #   total = full + (NG-1)*P_group + NG*(G-1)*P_layer
                NG = cfg.num_layers // G
                gspecs = jax.tree.map(
                    lambda s: _sds((G,) + s.shape[1:], s.dtype),
                    pshapes["layers"])

                def group_fwd(gp, x, sin, cos):
                    return tfm.dense_group_fwd(cfg, gp, x, sin, cos)

                out.append(Probe("group", _grad_probe(group_fwd),
                                 (gspecs, x_spec, sin_spec, sin_spec),
                                 (paxes["layers"], x_axes, (None, None),
                                  (None, None)),
                                 multiplier=NG - 1))
                out.append(Probe("layer", _grad_probe(layer_fwd, remat=True),
                                 (lspecs, x_spec, sin_spec, sin_spec),
                                 (laxes, x_axes, (None, None), (None, None)),
                                 multiplier=NG * (G - 1)))
            else:
                fn = _grad_probe(layer_fwd) if kind == "train" else layer_fwd
                out.append(Probe("layer", fn,
                                 (lspecs, x_spec, sin_spec, sin_spec),
                                 (laxes, x_axes, (None, None), (None, None)),
                                 multiplier=cfg.num_layers - 1))

        # attention inner-scan probes (chunked flash path, DESIGN.md §7)
        out.extend(_attention_chunk_probes(cfg, shape, B, Sfull, dt))
        if kind == "train":
            out.extend(_ce_chunk_probes(cfg, B, S, dt))
    else:  # decode
        sin_spec = _sds((1, cfg.head_dim // 2), jnp.float32)
        x_spec = _sds((B, 1, D), dt)
        x_axes = ("batch", None, None)
        pos_spec = _sds((), jnp.int32)
        # build the cache abstractly (jnp.zeros under eval_shape)
        holder = {}

        def mkcache():
            c, ax = tfm.init_cache(cfg, B, S)
            holder["ax"] = ax
            return c

        cache_shapes = jax.eval_shape(mkcache)
        cache_axes = holder["ax"]

        if cfg.block_pattern:
            pat = tuple(cfg.block_pattern)
            G = cfg.num_layers // len(pat)
            gspecs = _slice_specs(pshapes["groups"])
            gaxes = _slice_axes(paxes["groups"])
            cspecs = _slice_specs(cache_shapes["groups"])
            caxes = _slice_axes(cache_axes["groups"])

            def group_dec(gp, gc, x, sin, cos, pos):
                # mirror of lm_decode_step's gbody for one group slice
                body = _decode_group_body(cfg, pat)
                return body(gp, gc, x, sin, cos, pos)

            out.append(Probe("group_dec", group_dec,
                             (gspecs, cspecs, x_spec, sin_spec, sin_spec, pos_spec),
                             (gaxes, caxes, x_axes, (None, None), (None, None), ()),
                             multiplier=G - 1))
        else:
            lspecs = _slice_specs(pshapes["layers"])
            laxes = _slice_axes(paxes["layers"])
            kc = _sds(tuple(cache_shapes["k"].shape[1:]), cache_shapes["k"].dtype)
            vc = _sds(tuple(cache_shapes["v"].shape[1:]), cache_shapes["v"].dtype)
            kax = _slice_axes(cache_axes["k"])
            vax = _slice_axes(cache_axes["v"])

            def layer_dec(lp, kcache, vcache, x, sin, cos, pos):
                y, c2 = tfm._attn_decode(cfg, lp, x,
                                         {"k": kcache, "v": vcache},
                                         sin, cos, pos, None)
                y, _ = tfm._mlp_sub(cfg, lp, y, None)
                return y, c2["k"], c2["v"]

            out.append(Probe("layer_dec", layer_dec,
                             (lspecs, kc, vc, x_spec, sin_spec, sin_spec, pos_spec),
                             (laxes, kax, vax, x_axes, (None, None), (None, None), ()),
                             multiplier=cfg.num_layers - 1))
    return out


def _attention_chunk_probes(cfg, shape: ShapeSpec, B: int, S: int, dt,
                            tp: int = 16) -> List[Probe]:
    """Scan-body probes for the flash-in-XLA attention paths.

    The layer/group probe counts the attention scans' bodies once; the true
    program runs them nq (and nq*nk) times per attention layer. Multipliers:
        causal: qbody x n_att*(nq-1), kvbody x n_att*nq*(nk-1)
        window: qwin  x n_att*(nq-1)
    """
    import math as _math
    from repro.models import layers as nn

    out: List[Probe] = []
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    W = cfg.window_size
    train = shape.kind == "train"

    # number of attention layers
    if cfg.block_pattern:
        pat = tuple(cfg.block_pattern)
        G = cfg.num_layers // len(pat)
        n_att = sum(1 for k in pat if k == "attention") * G
        n_att += sum(1 for k in pat[:cfg.num_layers - G * len(pat)]
                     if k == "attention")
    elif cfg.family in ("dense", "moe", "vlm"):
        n_att = cfg.num_layers
    elif cfg.family == "audio":
        n_att = cfg.num_layers          # decoder self-attn (chunked one)
    else:
        n_att = 0
    if n_att == 0:
        return out

    if W and S > W:                      # sliding-window path
        qc = min(nn._CHUNK_Q, S)
        Sq = S + ((-S) % qc)
        nq = Sq // qc
        if nq <= 1:
            return out
        qt = _sds((B, H, Sq, hd), dt)
        ktp = _sds((B, KV, Sq + W, hd), dt)
        idx = _sds((), jnp.int32)

        def qwin(qt_, ktp_, vtp_, i):
            return nn.window_qbody_probe(qt_, ktp_, vtp_, i, W)

        fn = _grad_probe(qwin, remat=True) if train else qwin
        if cfg.num_heads % tp == 0:
            ax = ("batch", "heads", None, None)
            kax = ("batch", "kv_heads" if KV % tp == 0 else None, None, None)
        else:
            ax = ("batch_dm", None, None, None)
            kax = ("batch_dm", None, None, None)
        out.append(Probe("attn_qwin", fn, (qt, ktp, ktp, idx),
                         (ax, kax, kax, ()), multiplier=n_att * (nq - 1)))
        return out

    if S <= nn.CHUNKED_THRESHOLD:
        return out                       # exact path, no inner scans

    qc = min(nn._CHUNK_Q, S)
    kc = min(nn._CHUNK_K, S)
    Sq = S + ((-S) % qc)
    Sk = S + ((-S) % kc)
    nq, nk = Sq // qc, Sk // kc

    qblk = _sds((B, H, qc, hd), dt)
    kb = _sds((nk, B, KV, kc, hd), dt)
    kpos = _sds((nk, B, kc), jnp.int32)
    qpos = _sds((B, qc), jnp.int32)
    if H % tp == 0:
        bname = "batch"
        qax = ("batch", "heads", None, None)
        kvn = "kv_heads" if KV % tp == 0 else None
        kbax = (None, "batch", kvn, None, None)
    else:
        bname = "batch_dm"
        qax = ("batch_dm", None, None, None)
        kbax = (None, "batch_dm", None, None, None)

    if nq > 1:
        fn = (_grad_probe(nn.flash_qbody_probe, remat=True) if train
              else nn.flash_qbody_probe)
        out.append(Probe("attn_qbody", fn, (qblk, kb, kb, kpos, qpos),
                         (qax, kbax, kbax, (None, bname, None),
                          (bname, None)),
                         multiplier=n_att * (nq - 1)))
    if nk > 1:
        m = _sds((B, H, qc), jnp.float32)
        acc = _sds((B, H, qc, hd), jnp.float32)
        kblk = _sds((B, KV, kc, hd), dt)
        kp = _sds((B, kc), jnp.int32)
        fn = (_grad_probe(nn.flash_kvbody_probe, remat=True) if train
              else nn.flash_kvbody_probe)
        kax = kbax[1:]
        hax = qax[:3]
        out.append(Probe("attn_kvbody", fn,
                         (m, m, acc, kblk, kblk, kp, qblk, qpos),
                         (hax, hax, qax, kax, kax,
                          (bname, None), qax, (bname, None)),
                         multiplier=n_att * nq * (nk - 1)))
    return out


def _ce_chunk_probes(cfg: ModelConfig, B: int, S: int, dt) -> List[Probe]:
    """Streamed head+CE scan-body probe (train loss path)."""
    if S <= nn.CE_CHUNK:
        return []
    c = min(nn.CE_CHUNK, S)
    nc = (S + c - 1) // c
    if nc <= 1:
        return []
    D, V = cfg.d_model, cfg.vocab_size
    h = _sds((B, c, D), dt)
    tgt = _sds((B, c), jnp.int32)
    valid = _sds((B, c), jnp.bool_)
    carry = (_sds((), jnp.float32), _sds((), jnp.float32))
    if cfg.tie_embeddings:
        w = _sds((V, D), jnp.dtype(cfg.param_dtype))
        wax = ("vocab", "embed")
    else:
        w = _sds((D, V), jnp.dtype(cfg.param_dtype))
        wax = ("embed", "vocab")

    def ce(carry_, h_, tgt_, valid_, w_):
        return nn.ce_chunk_body(carry_, (h_, tgt_, valid_), w_,
                                cfg.tie_embeddings)[0]

    return [Probe("ce_chunk", _grad_probe(ce, remat=True),
                  (carry, h, tgt, valid, w),
                  (((), ()), ("batch", None, None), ("batch", None),
                   ("batch", None), wax),
                  multiplier=nc - 1)]


def _decode_group_body(cfg, pat):
    """Standalone one-group decode step used as probe (mirrors lm_decode_step)."""
    from repro.models import recurrent as rec_lib
    from repro.models import xlstm as xlstm_lib

    def body(gp, gc, x, sin, cos, pos):
        y = x
        for i, kind in enumerate(pat):
            name = f"b{i}_{kind}"
            lp, c = gp[name], gc[name]
            if kind == "attention":
                y, _ = tfm._attn_decode(
                    cfg, {"ln": lp["ln"], "core": lp["core"]},
                    y, c, sin, cos, pos, None, window=cfg.window_size)
                if "mlp" in lp:
                    y, _ = tfm._mlp_sub(cfg, lp, y, None)
            elif kind == "recurrent":
                h = nn.rms_norm(y, lp["ln"], cfg.norm_eps)
                o, _ = rec_lib.recurrent_block(
                    cfg, lp["core"], h, conv_state=c["conv"],
                    h_state=c["h"], decode=True)
                y = y + o
                if "mlp" in lp:
                    y, _ = tfm._mlp_sub(cfg, lp, y, None)
            elif kind == "mlstm":
                h = nn.rms_norm(y, lp["ln"], cfg.norm_eps)
                o, _ = xlstm_lib.mlstm_block(
                    cfg, lp["core"], h,
                    state=(c["conv"], (c["C"], c["n"], c["m"])), decode=True)
                y = y + o
            elif kind == "slstm":
                h = nn.rms_norm(y, lp["ln"], cfg.norm_eps)
                o, _ = xlstm_lib.slstm_block(
                    cfg, lp["core"], h,
                    state=(c["conv"], (c["c"], c["n2"], c["h"], c["m"])),
                    decode=True)
                y = y + o
        return y
    return body


# -- encoder-decoder (audio) ---------------------------------------------------

def _build_encdec(cfg: ModelConfig) -> Model:
    def init(key):
        return encdec_lib.init_encdec(key, cfg)

    def loss(params, batch, ctx):
        return encdec_lib.encdec_loss(cfg, params, batch, ctx)

    def prefill(params, batch, max_len, ctx):
        return encdec_lib.encdec_prefill(cfg, params, batch["frontend_embeds"],
                                         batch["tokens"], max_len, ctx)

    def decode(params, cache, tokens, pos, ctx):
        return encdec_lib.encdec_decode_step(cfg, params, cache, tokens, pos, ctx)

    def init_cache(batch, max_len, cache_dtype):
        return encdec_lib.init_encdec_cache(cfg, batch, max_len, cache_dtype)

    def probes(shape: ShapeSpec) -> List[Probe]:
        return _encdec_probes(cfg, shape)

    return Model(cfg, init, loss, prefill, decode, init_cache, probes)


def _encdec_probes(cfg: ModelConfig, shape: ShapeSpec) -> List[Probe]:
    B, S = shape.global_batch, shape.seq_len
    Se = cfg.frontend_seq
    dt = jnp.dtype(cfg.dtype)
    D = cfg.d_model
    model = build_model(cfg)
    pshapes, paxes = model.abstract_params()
    kind = shape.kind
    if kind in ("train", "prefill"):
        pshapes = jax.tree.map(lambda t: _sds(t.shape, jnp.bfloat16), pshapes)
    out: List[Probe] = []

    enc_specs = _slice_specs(pshapes["encoder"]["layers"])
    enc_axes = _slice_axes(paxes["encoder"]["layers"])
    dec_specs = _slice_specs(pshapes["decoder"]["layers"])
    dec_axes = _slice_axes(paxes["decoder"]["layers"])
    sin_e = _sds((Se, cfg.head_dim // 2), jnp.float32)

    def enc_layer(lp, x, sin, cos):
        h = nn.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = nn.qkv_project(cfg, lp["attn"], h)
        q = nn.apply_rope(q, sin, cos)
        k = nn.apply_rope(k, sin, cos)
        o = nn.causal_attention(q, k, v, causal=False)
        y = x + nn.out_project(cfg, lp["attn"], o)
        h2 = nn.rms_norm(y, lp["ln2"], cfg.norm_eps)
        return y + nn.mlp(cfg, lp["mlp"], h2)

    if kind in ("train", "prefill"):
        xe = _sds((B, Se, D), dt)
        xd = _sds((B, S, D), dt)
        sin_d = _sds((S, cfg.head_dim // 2), jnp.float32)
        eo = _sds((B, Se, D), dt)

        def dec_layer(lp, x, enc_out, sin, cos):
            h = nn.rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = nn.qkv_project(cfg, lp["attn"], h)
            q = nn.apply_rope(q, sin, cos)
            k = nn.apply_rope(k, sin, cos)
            o = tfm._attention_dispatch(cfg, q, k, v)
            y = x + nn.out_project(cfg, lp["attn"], o)
            hx = nn.rms_norm(y, lp["lnx"], cfg.norm_eps)
            qx, _, _ = nn.qkv_project(cfg, lp["xattn"], hx)
            _, kx, vx = nn.qkv_project(cfg, lp["xattn"], enc_out)
            ox = nn.causal_attention(qx, kx, vx, causal=False)
            y = y + nn.out_project(cfg, lp["xattn"], ox)
            h2 = nn.rms_norm(y, lp["ln2"], cfg.norm_eps)
            return y + nn.mlp(cfg, lp["mlp"], h2)

        ef = _grad_probe(enc_layer) if kind == "train" else enc_layer
        df = _grad_probe(dec_layer) if kind == "train" else dec_layer
        out.append(Probe("enc_layer", ef, (enc_specs, xe, sin_e, sin_e),
                         (enc_axes, ("batch", "seq", None), (None, None), (None, None)),
                         multiplier=cfg.encoder_layers - 1))
        out.append(Probe("dec_layer", df, (dec_specs, xd, eo, sin_d, sin_d),
                         (dec_axes, ("batch", "seq", None), ("batch", "seq", None),
                          (None, None), (None, None)),
                         multiplier=cfg.num_layers - 1))
        out.extend(_attention_chunk_probes(cfg, shape, B, S, dt))
    else:
        x = _sds((B, 1, D), dt)
        sin1 = _sds((1, cfg.head_dim // 2), jnp.float32)
        pos = _sds((), jnp.int32)
        KV, hd = cfg.num_kv_heads, cfg.head_dim
        kc = _sds((B, S, KV, hd), jnp.bfloat16)
        xk = _sds((B, Se, KV, hd), jnp.bfloat16)
        cax = ("batch", None, "kv_heads", "head_dim")

        def dec_step(lp, kcache, vcache, xkc, xvc, xx, sin, cos, p):
            h = nn.rms_norm(xx, lp["ln1"], cfg.norm_eps)
            q, k, v = nn.qkv_project(cfg, lp["attn"], h)
            q = nn.apply_rope(q, sin, cos)
            k = nn.apply_rope(k, sin, cos)
            kcache, vcache = nn.cache_update(kcache, vcache, k, v, p)
            o = nn.decode_attention(q, kcache, vcache, p)
            y = xx + nn.out_project(cfg, lp["attn"], o)
            hx = nn.rms_norm(y, lp["lnx"], cfg.norm_eps)
            qx, _, _ = nn.qkv_project(cfg, lp["xattn"], hx)
            ox = nn.decode_attention(qx, xkc, xvc, jnp.asarray(Se - 1))
            y = y + nn.out_project(cfg, lp["xattn"], ox)
            h2 = nn.rms_norm(y, lp["ln2"], cfg.norm_eps)
            return y + nn.mlp(cfg, lp["mlp"], h2), kcache, vcache

        out.append(Probe("dec_step", dec_step,
                         (dec_specs, kc, kc, xk, xk, x, sin1, sin1, pos),
                         (dec_axes, cax, cax, cax, cax,
                          ("batch", None, None), (None, None), (None, None), ()),
                         multiplier=cfg.num_layers - 1))
    return out
