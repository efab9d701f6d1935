"""The comparison that decides `correct` for a served model, shared by
every architecture.

A served token is compared by its logit under the plain float32 reference
(`ref`, the module that the configuration's `reference` key names, with
the whole reference weight dict `w`): at the position that produced it,
the gap between the reference's best logit and the reference's logit of
the served token.
A greedy server that computes what the reference computes serves the
reference's best token, or one whose logit lies within rounding of it; a
server that alters a token, skips a layer or reads a stale cache serves
tokens far below the best. The number compared is the widest such gap over
the sampled requests (`max_logit_gap`).

The control puts the reference in the program's place at the precision
below the one the configuration states (bfloat16 compute -> float8): at
each position of the same prompts and served tokens it takes the token the
float8 reference puts first and reads that token's gap under the float32
reference (`control_max_logit_gap`).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 256


@functools.partial(jax.jit, static_argnums=(0, 4))
def _block_gaps(logits, w, h_ref, h_ctl, quant, served, valid):
    """Gaps of one block of rows: (served gap, control gap), -inf on
    invalid rows. The leaves of `w` that `logits` does not read are
    pruned from the compiled program."""
    ref = logits(w, h_ref)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    served_gap = jnp.where(valid, best - got, -jnp.inf)
    if h_ctl is None:
        return served_gap, jnp.full_like(served_gap, -jnp.inf)
    pick = jnp.argmax(logits(w, h_ctl, quant), axis=-1)
    ctl = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return served_gap, jnp.where(valid, best - ctl, -jnp.inf)


def request_gaps(ref, w, cfg, prompt: np.ndarray, served: Sequence[int],
                 pad_to: int, control: Optional[str] = None
                 ) -> Tuple[float, Optional[float]]:
    """(widest served gap, widest control gap or None) of one request.

    The reference runs once over the prompt followed by the served tokens
    (the last one excepted), right-padded to `pad_to` positions so that
    every request reuses one compiled program; causal attention keeps the
    padding out of every real position."""
    served = np.asarray(served, np.int32)
    n, p = len(served), len(prompt)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} exceeds pad_to={pad_to}")
    toks = np.zeros((pad_to,), np.int32)
    toks[:len(seq)] = seq
    h_ref = ref.hidden(w, cfg, toks)
    h_ctl = ref.hidden(w, cfg, toks, control) if control else None
    # rows p-1 .. p+n-2 produced served tokens 0 .. n-1
    rows = np.arange(p - 1, p - 1 + n)
    nb = -(-n // ROW_BLOCK) * ROW_BLOCK
    idx = np.full((nb,), p - 1, np.int32)
    idx[:n] = rows
    tgt = np.zeros((nb,), np.int32)
    tgt[:n] = served
    valid = np.arange(nb) < n
    worst, worst_ctl = -np.inf, -np.inf
    for b in range(0, nb, ROW_BLOCK):
        sl = slice(b, b + ROW_BLOCK)
        hr = h_ref[idx[sl]]
        hc = h_ctl[idx[sl]] if h_ctl is not None else None
        g, gc = _block_gaps(ref.logits, w, hr, hc, control,
                            jnp.asarray(tgt[sl]), jnp.asarray(valid[sl]))
        worst = max(worst, float(jnp.max(g)))
        worst_ctl = max(worst_ctl, float(jnp.max(gc)))
    return worst, (worst_ctl if control else None)


def sample_requests(done: List[Tuple[np.ndarray, List[int]]], seed: int,
                    count: int) -> List[int]:
    """Indices of `count` finished requests drawn from the seed, the one
    with the most served tokens always among them."""
    if not done:
        return []
    longest = int(np.argmax([len(s) for _, s in done]))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    pick = rng.choice(len(rest), size=min(count - 1, len(rest)),
                      replace=False) if rest and count > 1 else []
    return [longest] + [rest[int(i)] for i in pick]


def gap_readings(ref, w, cfg, sample: List[Tuple[np.ndarray, List[int]]],
                 pad_to: int, control: Optional[str] = None
                 ) -> Dict[str, float]:
    """The compared numbers over a sample of (prompt, served tokens)."""
    out = {"max_logit_gap": -np.inf, "served_tokens": 0}
    if control:
        out["control_max_logit_gap"] = -np.inf
    for prompt, served in sample:
        g, gc = request_gaps(ref, w, cfg, prompt, served, pad_to, control)
        out["max_logit_gap"] = max(out["max_logit_gap"], g)
        out["served_tokens"] += len(served)
        if control:
            out["control_max_logit_gap"] = max(out["control_max_logit_gap"],
                                               gc)
    return out
