"""The plain float32 Qwen2 reference against `repro.models` at a small size
on the CPU: prefill then decode through the KV cache, and the training loss
with its gradient. Both sides get the same weights from
`perf/weights/qwen2.py`."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from perf.reference import qwen2  # noqa: E402
from perf.weights import qwen2 as weights  # noqa: E402

CFG = {"name": "tiny", "hidden_act": "silu", "hidden_size": 64,
       "intermediate_size": 96, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "vocab_size": 101, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
       "tie_word_embeddings": True, "param_dtype": "float32",
       "dtype": "float32"}
SEED = 2**33 + 17
PROMPT, DECODE = 7, 5


@pytest.fixture(scope="module")
def model():
    from repro.models import build_model
    return build_model(weights.model_config(CFG))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(3).integers(0, CFG["vocab_size"],
                                             PROMPT + DECODE).astype(np.int32)


def _rel(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                 / np.max(np.abs(np.asarray(b))))


def test_weights_fill_the_program_tree_and_keep_every_seed_bit(model):
    prog = weights.program_weights(CFG, SEED)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(prog) == jax.tree.structure(want)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in
               zip(jax.tree.leaves(prog), jax.tree.leaves(want)))
    other = weights.reference_weights(CFG, SEED + 2**32)
    same = weights.reference_weights(CFG, SEED)
    assert not np.allclose(other["embed"], same["embed"])


def test_prefill_of_every_prefix_matches_the_reference(model, tokens):
    """float32 compute: the program's prefill logits at the last position of
    each prefix agree with the reference's logits at that position to
    float32 rounding (both at "highest" matmul precision)."""
    w = weights.reference_weights(CFG, SEED)
    params = weights.program_weights(CFG, SEED)
    ref = qwen2.forward_logits(w, CFG, tokens)
    with jax.default_matmul_precision("highest"):
        for n in range(1, len(tokens) + 1):
            got, _ = model.prefill(params, {"tokens": jnp.asarray(tokens[None, :n])},
                                   len(tokens) + 1)
            assert _rel(got[0], ref[n - 1]) < 1e-4, n


def test_prefill_then_decode_through_the_cache(model, tokens):
    """The KV cache is bfloat16 in the program, so decode logits carry its
    rounding (2^-8 relative on every K and V): 2e-2 of the logit range."""
    w = weights.reference_weights(CFG, SEED)
    params = weights.program_weights(CFG, SEED)
    ref = qwen2.forward_logits(w, CFG, tokens)
    max_len = len(tokens) + 1
    with jax.default_matmul_precision("highest"):
        logits, cache = model.prefill(
            params, {"tokens": jnp.asarray(tokens[None, :PROMPT])}, max_len)
        assert _rel(logits[0], ref[PROMPT - 1]) < 1e-4
        for i in range(PROMPT, len(tokens)):
            logits, cache = model.decode_step(
                params, cache, jnp.asarray(tokens[None, i]),
                jnp.asarray(i, jnp.int32))
            assert _rel(logits[0], ref[i]) < 2e-2, i


def test_training_loss_and_gradient(model, tokens):
    """The program's loss is the reference's cross-entropy plus its z-loss
    regularizer (1e-4 * mean(logsumexp^2), a departure of the program);
    the gradient through the program's parameter tree agrees leaf by leaf."""
    from perf.weights.qwen2 import _to_program
    w = weights.reference_weights(CFG, SEED)
    inp, tgt = tokens[:-1], tokens[1:]
    batch = {"tokens": jnp.asarray(inp[None]), "targets": jnp.asarray(tgt[None])}

    def ref_loss(w):
        lg = qwen2.logits(w, qwen2.hidden(w, CFG, inp))
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        return qwen2.loss(w, CFG, inp, tgt) + 1e-4 * jnp.mean(lse * lse)

    def prog_loss(w):
        return model.loss(_to_program(CFG, w), batch)[0]

    with jax.default_matmul_precision("highest"):
        lr, gr = jax.value_and_grad(ref_loss)(w)
        lp, gp = jax.value_and_grad(prog_loss)(w)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert _rel(a, b) < 1e-3


def test_fp8_control_departs_from_the_reference(tokens):
    w = weights.reference_weights(CFG, SEED)
    ref = qwen2.forward_logits(w, CFG, tokens)
    ctl = qwen2.forward_logits(w, CFG, tokens, quant="fp8")
    assert 1e-3 < _rel(ctl, ref) < 0.5
