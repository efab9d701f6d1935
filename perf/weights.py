"""Qwen2 weights made from a seed, on the device, in one jitted call.

The benchmark makes the weights; neither the program under test nor the
plain reference makes its own. `reference_weights` gives them in the
published layout (the layout of `reference/qwen2.py`), and
`program_weights` gives the same numbers rearranged into the parameter
tree of `repro.models` (stacked layers, per-head projections, norm scales
stored as `scale - 1`). Both are drawn from the same key, so the two sides
compute with the same model.

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


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed of up to 64 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


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
