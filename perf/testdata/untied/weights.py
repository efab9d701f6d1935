"""Weights of the harness tests' second architecture (a decoder with no
QKV bias and an untied head), made from a seed in one jitted call, and the
program's model configuration for it.

`reference_weights` gives them in the layout of `reference.py` beside this
file, with the head as `lm_head` (vocab, hidden); `program_weights` gives
the same numbers in the parameter tree of `repro.models`, where the untied
head is `embed.head` (hidden, vocab).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perf.weights import seed_key


def model_config(cfg: Dict[str, Any]):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=False, tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        mlp_act="swiglu", dtype=cfg["dtype"], param_dtype=cfg["param_dtype"])


def _draw(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    L, D, F, V = (cfg["num_hidden_layers"], cfg["hidden_size"],
                  cfg["intermediate_size"], cfg["vocab_size"])
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    return {
        "embed": normal((V, D), 0.02),
        "lm_head": normal((V, D), 1.0 / math.sqrt(D)),
        "final_norm": 1.0 + normal((D,), 0.1),
        "layers": {
            "input_norm": 1.0 + normal((L, D), 0.1),
            "q_w": normal((L, D, q), 1.0 / math.sqrt(D)),
            "k_w": normal((L, D, kv), 1.0 / math.sqrt(D)),
            "v_w": normal((L, D, kv), 1.0 / math.sqrt(D)),
            "o_w": normal((L, q, D), 1.0 / math.sqrt(q)),
            "post_norm": 1.0 + normal((L, D), 0.1),
            "gate_w": normal((L, D, F), 1.0 / math.sqrt(D)),
            "up_w": normal((L, D, F), 1.0 / math.sqrt(D)),
            "down_w": normal((L, F, D), 1.0 / math.sqrt(F)),
        },
    }


def _to_program(cfg: Dict[str, Any], w: Dict[str, Any]) -> Dict[str, Any]:
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    lw = w["layers"]
    return {
        "embed": {"tok": w["embed"], "head": w["lm_head"].T},
        "final_ln": w["final_norm"] - 1.0,
        "layers": {
            "ln1": lw["input_norm"] - 1.0,
            "ln2": lw["post_norm"] - 1.0,
            "attn": {"wq": lw["q_w"].reshape(L, D, H, hd),
                     "wk": lw["k_w"].reshape(L, D, KV, hd),
                     "wv": lw["v_w"].reshape(L, D, KV, hd),
                     "wo": lw["o_w"].reshape(L, H, hd, D)},
            "mlp": {"w_gate": lw["gate_w"], "w_up": lw["up_w"],
                    "w_down": lw["down_w"]},
        },
    }


def reference_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    return jax.jit(lambda k: _draw(cfg, k))(seed_key(seed))


def program_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    dt = jnp.dtype(cfg["param_dtype"])

    def make(k):
        tree = _to_program(cfg, _draw(cfg, k))
        return jax.tree.map(lambda x: x.astype(dt), tree)

    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))
