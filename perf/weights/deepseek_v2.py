"""Weights of the DeepSeek-V2 configurations, made from a seed on the device
in one jitted call, and the program's model configuration for them: the
`weights` module that `configs/deepseek-v2-lite-ep8.json` names.

`reference_weights` gives them in the published layout (the layout of
`reference/deepseek_v2.py`: HF DeepseekV2's matrices transposed to
(in, out), the rope slices of `q_proj` and `kv_a_proj_with_mqa` in their
interleaved order), holding the routed experts this chip holds;
`program_weights` gives the same numbers in the parameter tree of
`repro.models` (stacked layers, the leading dense layers apart, per-head
projections, the rope columns de-interleaved, `kv_b_proj` split into its
key and value halves, norm scales stored as `scale - 1`). Both are drawn
from the same key, so the two sides compute with the same model.

Scales: the projections are normal with std 1/sqrt(fan_in), the embedding
0.02, the norm scales 1 + 0.1 * normal; the router at std 1/sqrt(hidden)
gives logits of about unit spread, so that the top-6 of 64 is decided by
clear margins on most tokens.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from perf.weights import seed_key

YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim")


def _check(cfg: Dict[str, Any]) -> None:
    """The published settings this module and the program implement."""
    want = {"q_lora_rank": None, "topk_method": "greedy", "n_group": 1,
            "topk_group": 1, "scoring_func": "softmax",
            "routed_scaling_factor": 1, "moe_layer_freq": 1,
            "attention_bias": False, "hidden_act": "silu"}
    bad = {k: cfg.get(k) for k, v in want.items() if cfg.get(k) != v}
    if bad or cfg["rope_scaling"].get("type") != "yarn":
        raise ValueError(f"unsupported DeepSeek-V2 settings: {bad}")


def model_config(cfg: Dict[str, Any]):
    """The program's model configuration, from the configuration file."""
    from repro.configs.base import ModelConfig
    _check(cfg)
    rs = cfg["rope_scaling"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        rope_scaling=tuple((k, float(rs[k])) for k in YARN_KEYS),
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        num_experts=cfg["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        router_experts=cfg["n_routed_experts_published"],
        expert_offset=cfg["expert_offset"],
        moe_d_ff=cfg["moe_intermediate_size"],
        shared_d_ff=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        moe_raw_topk=not cfg["norm_topk_prob"],
        first_dense_layers=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"],
        mlp_act="swiglu", dtype=cfg["dtype"], param_dtype=cfg["param_dtype"])


def _draw(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    L, Ld = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    Lm = L - Ld
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, R = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    Fd, Fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    Fs = Fe * cfg["n_shared_experts"]
    E, Er = cfg["n_routed_experts"], cfg["n_routed_experts_published"]
    ks = iter(jax.random.split(key, 32))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    def norm(shape):
        return 1.0 + normal(shape, 0.1)

    return {
        "embed": normal((V, D), 0.02),
        "lm_head": normal((V, D), 1.0 / math.sqrt(D)),
        "final_norm": norm((D,)),
        "layers": {
            "input_norm": norm((L, D)),
            "q_w": normal((L, D, H * (dn + dr)), 1.0 / math.sqrt(D)),
            "kv_a_w": normal((L, D, R + dr), 1.0 / math.sqrt(D)),
            "kv_a_norm": norm((L, R)),
            "kv_b_w": normal((L, R, H * (dn + dv)), 1.0 / math.sqrt(R)),
            "o_w": normal((L, H * dv, D), 1.0 / math.sqrt(H * dv)),
            "post_norm": norm((L, D)),
        },
        "dense": {
            "gate_w": normal((Ld, D, Fd), 1.0 / math.sqrt(D)),
            "up_w": normal((Ld, D, Fd), 1.0 / math.sqrt(D)),
            "down_w": normal((Ld, Fd, D), 1.0 / math.sqrt(Fd)),
        },
        "moe": {
            "router_w": normal((Lm, D, Er), 1.0 / math.sqrt(D)),
            "gate_w": normal((Lm, E, D, Fe), 1.0 / math.sqrt(D)),
            "up_w": normal((Lm, E, D, Fe), 1.0 / math.sqrt(D)),
            "down_w": normal((Lm, E, Fe, D), 1.0 / math.sqrt(Fe)),
            "shared_gate_w": normal((Lm, D, Fs), 1.0 / math.sqrt(D)),
            "shared_up_w": normal((Lm, D, Fs), 1.0 / math.sqrt(D)),
            "shared_down_w": normal((Lm, Fs, D), 1.0 / math.sqrt(Fs)),
        },
    }


def deinterleave(d: int) -> np.ndarray:
    """Column order that turns an interleaved rope slice (pairs 2i, 2i+1)
    into rotate-half order (first the even, then the odd columns): HF's
    `view(d/2, 2).transpose(-1, -2).reshape(d)`."""
    return np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])


def _to_program(cfg: Dict[str, Any], w: Dict[str, Any]) -> Dict[str, Any]:
    L, Ld = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    D, H, R = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    perm = deinterleave(dr)
    lw = w["layers"]
    q = lw["q_w"].reshape(L, D, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], q[..., dn:][..., perm]], axis=-1)
    kv_a = jnp.concatenate([lw["kv_a_w"][..., :R],
                            lw["kv_a_w"][..., R:][..., perm]], axis=-1)
    kv_b = lw["kv_b_w"].reshape(L, R, H, dn + dv)
    per_layer = {
        "ln1": lw["input_norm"] - 1.0,
        "ln2": lw["post_norm"] - 1.0,
        "attn": {"wq": q, "wkv_a": kv_a, "kv_norm": lw["kv_a_norm"] - 1.0,
                 "wk_b": kv_b[..., :dn], "wv_b": kv_b[..., dn:],
                 "wo": lw["o_w"].reshape(L, H, dv, D)},
    }
    dense = jax.tree.map(lambda a: a[:Ld], per_layer)
    dense["mlp"] = {"w_gate": w["dense"]["gate_w"],
                    "w_up": w["dense"]["up_w"],
                    "w_down": w["dense"]["down_w"]}
    moe = jax.tree.map(lambda a: a[Ld:], per_layer)
    m = w["moe"]
    moe["mlp"] = {"router": m["router_w"], "w_gate": m["gate_w"],
                  "w_up": m["up_w"], "w_down": m["down_w"],
                  "shared": {"w_gate": m["shared_gate_w"],
                             "w_up": m["shared_up_w"],
                             "w_down": m["shared_down_w"]}}
    out = {"embed": {"tok": w["embed"], "head": w["lm_head"].T},
           "final_ln": w["final_norm"] - 1.0, "layers": moe}
    if Ld:
        out["dense_layers"] = dense
    return out


def reference_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Float32 weights in the published layout."""
    return jax.jit(lambda k: _draw(cfg, k))(seed_key(seed))


def program_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The same weights in the parameter tree of `repro.models`, in the
    configuration's master dtype."""
    dt = jnp.dtype(cfg["param_dtype"])

    def make(k):
        tree = _to_program(cfg, _draw(cfg, k))
        return jax.tree.map(lambda x: x.astype(dt), tree)

    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))
