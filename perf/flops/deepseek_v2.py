"""Operations and bytes a DeepSeek-V2 decoder needs at one chip's share of
its experts, from shapes alone: the `flops` module that
`configs/deepseek-v2-lite-ep8.json` names.

Model FLOPs count each multiply-add as 2 and count what the model needs,
once: not per replica, not padding, not recomputation, and not the held
experts' rows that no token was routed to. `cfg` is a configuration
file's dict (published DeepseekV2 keys). The harness calls
`serving_flops` and `prefill_lane_bytes`; the rest are their terms.

Attention: prefill in the expanded form (each prompt token's latent
up-projected into per-head keys and values, which every later query of the
prompt attends); decode in the absorbed form (W_uk folded into the query
and W_uv into the output, attention over the latent cache).

MoE: per token and MoE layer the router, the shared experts, and the
routed experts' expected share on this chip under uniform routing,
`num_experts_per_tok * n_routed_experts / n_routed_experts_published`
experts (6 * 8/64 = 0.75 here); the routing of the traffic decides the
real share, which `expert_rows_useful_pct.serve` reads from the program's
counters.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

BF16_BYTES = 2


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def _layers(cfg) -> Tuple[int, int]:
    """(dense layers, MoE layers)."""
    Ld = cfg["first_k_dense_replace"]
    return Ld, cfg["num_hidden_layers"] - Ld


def attention_params(cfg: Dict[str, Any], expanded: bool) -> int:
    """Weights one token multiplies through in one MLA layer: the query,
    the latent down-projection and rope key, the output, and either the
    latent's up-projection (expanded) or the absorbed W_uk and W_uv."""
    D, H, R, dn, dr, dv = _dims(cfg)
    shared = D * H * (dn + dr) + D * (R + dr) + H * dv * D
    if expanded:
        return shared + R * H * (dn + dv)
    return shared + H * dn * R + H * R * dv


def mlp_params(cfg: Dict[str, Any], moe: bool) -> float:
    D = cfg["hidden_size"]
    if not moe:
        return 3 * D * cfg["intermediate_size"]
    Fe = cfg["moe_intermediate_size"]
    held = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["n_routed_experts_published"])
    return (D * cfg["n_routed_experts_published"]
            + 3 * D * Fe * cfg["n_shared_experts"] + held * 3 * D * Fe)


def trunk_flops(cfg: Dict[str, Any], expanded: bool) -> float:
    """One token's matrix products through every layer, no attention
    core, no head."""
    Ld, Lm = _layers(cfg)
    att = attention_params(cfg, expanded)
    return 2 * (Ld * (att + mlp_params(cfg, False))
                + Lm * (att + mlp_params(cfg, True)))


def head_params(cfg: Dict[str, Any]) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def attention_core_flops(cfg: Dict[str, Any], keys: int,
                         expanded: bool) -> int:
    """QK^T and PV of one query token over `keys` positions, all layers:
    per head and key, (dn + dr) + dv expanded, (R + dr) + R absorbed."""
    _D, H, R, dn, dr, dv = _dims(cfg)
    per_key = (dn + dr + dv) if expanded else (2 * R + dr)
    return 2 * H * per_key * keys * cfg["num_hidden_layers"]


def prefill_flops(cfg: Dict[str, Any], prompt_len: int) -> float:
    """A prompt's forward pass, with logits for its last position only."""
    n = int(prompt_len)
    # causal: query i attends i + 1 keys
    return (trunk_flops(cfg, True) * n
            + attention_core_flops(cfg, 1, True) * n * (n + 1) // 2
            + 2 * head_params(cfg))


def decode_flops(cfg: Dict[str, Any], position: int) -> float:
    """One decode token at 0-based `position` (attends position + 1 keys)."""
    return (trunk_flops(cfg, False) + 2 * head_params(cfg)
            + attention_core_flops(cfg, int(position) + 1, False))


def served_request_flops(cfg: Dict[str, Any], prompt_len: int,
                         served: int) -> float:
    """Prefill of the prompt (which yields the first served token) and the
    decode steps that yield the other served - 1 tokens."""
    n = int(served)
    p = int(prompt_len)
    # decode positions p .. p + n - 2: the attention core is linear in them
    keys = sum(range(p + 1, p + n))
    return (prefill_flops(cfg, p)
            + (n - 1) * (trunk_flops(cfg, False) + 2 * head_params(cfg))
            + attention_core_flops(cfg, keys, False))


def serving_flops(cfg: Dict[str, Any],
                  requests: Iterable[Tuple[int, int]]) -> float:
    """Model FLOPs of (prompt_len, served tokens) pairs."""
    return sum(served_request_flops(cfg, p, n) for p, n in requests if n)


def latent_row_bytes(cfg: Dict[str, Any], max_len: int,
                     itemsize: int = BF16_BYTES) -> int:
    """One sequence's latent cache (c_kv and k_pe) over every layer and
    position."""
    return (cfg["num_hidden_layers"] * int(max_len)
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize)


def prefill_lane_bytes(cfg: Dict[str, Any], max_len: int, rows: int,
                       replicas: int, itemsize: int = BF16_BYTES) -> int:
    """Bytes a packed prefill's validation covers: per row and replica,
    the row's logits and its latent cache rows."""
    per_row = cfg["vocab_size"] * itemsize + latent_row_bytes(cfg, max_len,
                                                              itemsize)
    return int(rows) * int(replicas) * per_row
