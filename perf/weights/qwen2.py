"""Weights of the qwen2 configurations, made from a seed on the device in
one jitted call, and the program's model configuration for them: the
`weights` module that `configs/qwen2-0.5b.json` names.

The benchmark makes the weights; neither the program under test nor the
plain reference makes its own. `reference_weights` gives them in the
published layout (the layout of `reference/qwen2.py`), and
`program_weights` gives the same numbers rearranged into the parameter
tree of `repro.models` (stacked layers, per-head projections, norm scales
stored as `scale - 1`). Both are drawn from the same key, so the two sides
compute with the same model. `model_config` maps the published keys onto
the program's `ModelConfig`.

Scales: the projections are normal with std 1/sqrt(fan_in), the embedding
0.02, the biases 0.02 and the norm scales 1 + 0.1 * normal, so that every
parameter of the published block (the QKV biases and the norm scales
among them) takes part in the result.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perf.weights import seed_key


def model_config(cfg: Dict[str, Any]):
    """The program's model configuration, from the configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=True, tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        mlp_act="swiglu" if cfg["hidden_act"] == "silu" else cfg["hidden_act"],
        dtype=cfg["dtype"], param_dtype=cfg["param_dtype"])


def _draw(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    L = cfg["num_hidden_layers"]
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    KV = cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    F = cfg["intermediate_size"]
    V = cfg["vocab_size"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    return {
        "embed": normal((V, D), 0.02),
        "final_norm": 1.0 + normal((D,), 0.1),
        "layers": {
            "input_norm": 1.0 + normal((L, D), 0.1),
            "q_w": normal((L, D, H * hd), 1.0 / math.sqrt(D)),
            "q_b": normal((L, H * hd), 0.02),
            "k_w": normal((L, D, KV * hd), 1.0 / math.sqrt(D)),
            "k_b": normal((L, KV * hd), 0.02),
            "v_w": normal((L, D, KV * hd), 1.0 / math.sqrt(D)),
            "v_b": normal((L, KV * hd), 0.02),
            "o_w": normal((L, H * hd, D), 1.0 / math.sqrt(H * hd)),
            "post_norm": 1.0 + normal((L, D), 0.1),
            "gate_w": normal((L, D, F), 1.0 / math.sqrt(D)),
            "up_w": normal((L, D, F), 1.0 / math.sqrt(D)),
            "down_w": normal((L, F, D), 1.0 / math.sqrt(F)),
        },
    }


def _to_program(cfg: Dict[str, Any], w: Dict[str, Any]) -> Dict[str, Any]:
    L = cfg["num_hidden_layers"]
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    KV = cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    lw = w["layers"]
    return {
        "embed": {"tok": w["embed"]},
        "final_ln": w["final_norm"] - 1.0,
        "layers": {
            "ln1": lw["input_norm"] - 1.0,
            "ln2": lw["post_norm"] - 1.0,
            "attn": {
                "wq": lw["q_w"].reshape(L, D, H, hd),
                "wk": lw["k_w"].reshape(L, D, KV, hd),
                "wv": lw["v_w"].reshape(L, D, KV, hd),
                "wo": lw["o_w"].reshape(L, H, hd, D),
                "bq": lw["q_b"].reshape(L, H, hd),
                "bk": lw["k_b"].reshape(L, KV, hd),
                "bv": lw["v_b"].reshape(L, KV, hd),
            },
            "mlp": {"w_gate": lw["gate_w"], "w_up": lw["up_w"],
                    "w_down": lw["down_w"]},
        },
    }


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
