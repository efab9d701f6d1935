"""The qwen2 configuration's modules, reached through its configuration
file, count, draw and compare as the harness did before it named them
there: values recorded from the commit where `perf/flops.py`,
`perf/weights.py` and `perf/check.py` knew Qwen2 themselves."""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from perf import check  # noqa: E402
from perf import run as bench_run  # noqa: E402

# (prompt_len, served tokens) of a fixed call
WORK = [(64, 16), (1024, 128), (2048, 256), (300, 1), (700, 0), (1500, 77)]
TINY = {"name": "tiny", "source": "test", "hidden_act": "silu",
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 257, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
        "tie_word_embeddings": True, "param_dtype": "float32",
        "dtype": "bfloat16", "cache_dtype": "bfloat16", "reduced": []}
SEED = 2**33 + 17


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perf", "configs", "qwen2-0.5b.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def arch(cfg):
    return bench_run.load_arch(ROOT, cfg)


def _digest(tree):
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def test_flops_and_bytes_of_the_published_config(arch, cfg):
    assert arch.flops.serving_flops(cfg, WORK) == 4397389717504
    for rows, replicas, want in [(1, 1, 28713728), (4, 2, 229709824),
                                 (2, 2, 114854912)]:
        assert arch.flops.prefill_lane_bytes(cfg, 2312, rows,
                                             replicas) == want


def test_weights_are_drawn_bit_for_bit(arch):
    """Elementwise draws and reshapes: the same bits on any CPU."""
    assert _digest(arch.weights.reference_weights(TINY, SEED)) == \
        "6723c5f8cdac843f10037ec22351b3a5b470b0dee5ee063e8441482927f70075"
    assert _digest(arch.weights.program_weights(TINY, SEED)) == \
        "c4d1ac3100d9f03cb5c74c349a750e9c09071723399bae1d7e51748f22d828b7"


def test_request_gaps_of_one_request(arch):
    """The served and control gaps of one tiny request. Recorded on an x86
    CPU; the matrix products' rounding may differ by an instruction set, so
    the values are held to float32 rounding of the logits, not to the bit."""
    w = arch.weights.reference_weights(TINY, SEED)
    prompt = np.arange(3, 14, dtype=np.int32) * 7 % 257
    served = [5, 200, 17, 99, 3]
    gap, ctl = check.request_gaps(arch.reference, w, TINY, prompt, served,
                                  32, "fp8")
    assert gap == pytest.approx(0.47282251715660095, abs=1e-6)
    assert ctl == pytest.approx(0.022920280694961548, abs=1e-6)
    assert check.request_gaps(arch.reference, w, TINY, prompt, served,
                              32) == (gap, None)
