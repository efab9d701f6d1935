"""Operations and bytes a qwen2 decoder needs, from shapes alone: the
`flops` module that `configs/qwen2-0.5b.json` names.

Model FLOPs count each multiply-add as 2 and count what the model needs,
once: not per replica, not padding, not recomputation. `cfg` is a
configuration file's dict (published Qwen2 keys). The harness calls
`serving_flops` and `prefill_lane_bytes`; the rest are their terms.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

BF16_BYTES = 2


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights one token multiplies through in one decoder layer."""
    D = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    F = cfg["intermediate_size"]
    return D * q + 2 * D * kv + q * D + 3 * D * F


def head_params(cfg: Dict[str, Any]) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def attention_flops(cfg: Dict[str, Any], keys: int) -> int:
    """QK^T and PV of one query token over `keys` positions, all layers."""
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"] * keys
            * cfg["num_hidden_layers"])


def prefill_flops(cfg: Dict[str, Any], prompt_len: int) -> int:
    """A prompt's forward pass, with logits for its last position only."""
    n = int(prompt_len)
    trunk = 2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * n
    # causal: query i attends i + 1 keys
    attn = attention_flops(cfg, 1) * n * (n + 1) // 2
    return trunk + attn + 2 * head_params(cfg)


def decode_flops(cfg: Dict[str, Any], position: int) -> int:
    """One decode token at 0-based `position` (attends position + 1 keys)."""
    return (2 * (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                 + head_params(cfg))
            + attention_flops(cfg, int(position) + 1))


def served_request_flops(cfg: Dict[str, Any], prompt_len: int,
                         served: int) -> int:
    """Prefill of the prompt (which yields the first served token) and the
    decode steps that yield the other served - 1 tokens."""
    total = prefill_flops(cfg, prompt_len)
    for i in range(1, int(served)):
        total += decode_flops(cfg, prompt_len + i - 1)
    return total


def serving_flops(cfg: Dict[str, Any],
                  requests: Iterable[Tuple[int, int]]) -> int:
    """Model FLOPs of (prompt_len, served tokens) pairs."""
    return sum(served_request_flops(cfg, p, n) for p, n in requests if n)


def kv_row_bytes(cfg: Dict[str, Any], max_len: int,
                 itemsize: int = BF16_BYTES) -> int:
    """One sequence's K and V cache over every layer and position."""
    return (2 * cfg["num_hidden_layers"] * int(max_len)
            * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize)


def prefill_lane_bytes(cfg: Dict[str, Any], max_len: int, rows: int,
                       replicas: int, itemsize: int = BF16_BYTES) -> int:
    """Bytes a packed prefill's validation covers: per row and replica,
    the row's logits and its cache rows."""
    per_row = cfg["vocab_size"] * itemsize + kv_row_bytes(cfg, max_len,
                                                          itemsize)
    return int(rows) * int(replicas) * per_row
