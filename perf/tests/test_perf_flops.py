"""Operation and byte counts of `perf/flops/qwen2.py` against hand counts."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf.flops import qwen2 as flops  # noqa: E402

# 2 layers, hidden 8, 2 query heads and 1 KV head of 4, MLP 16, vocab 10
CFG = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
       "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
       "vocab_size": 10}


def test_weights_one_token_multiplies_through():
    # q 8x8, k and v 8x4 each, o 8x8, gate/up/down 8x16 each
    assert flops.layer_matmul_params(CFG) == 64 + 32 + 32 + 64 + 3 * 128
    assert flops.head_params(CFG) == 80


def test_prefill_and_decode_flops():
    # per key: QK^T and PV, 2 flops each, 2 heads x 4 dims, 2 layers
    assert flops.attention_flops(CFG, 1) == 64
    # 3 prompt tokens through 2 layers, causal 1+2+3 keys, one head row
    assert flops.prefill_flops(CFG, 3) == 2 * 2 * 576 * 3 + 64 * 6 + 2 * 80
    # the token at position 3 attends 4 keys
    assert flops.decode_flops(CFG, 3) == 2 * (2 * 576 + 80) + 64 * 4
    # 3 served tokens: prefill, then decode at positions 3 and 4
    assert flops.served_request_flops(CFG, 3, 3) == 7456 + 2720 + 2784
    assert flops.serving_flops(CFG, [(3, 3), (5, 0), (3, 1)]) == \
        12960 + flops.prefill_flops(CFG, 3)


def test_validation_bytes():
    # K and V, 2 layers, 5 positions, 1 head of 4, bfloat16
    assert flops.kv_row_bytes(CFG, 5) == 2 * 2 * 5 * 4 * 2
    # 4 rows, 2 replicas, each row its logits (10 x 2 B) and cache rows
    assert flops.prefill_lane_bytes(CFG, 5, rows=4, replicas=2) == \
        4 * 2 * (20 + 160)
