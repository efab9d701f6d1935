"""Saturating backlog: each serve() call is handed a batch of requests that
are all due at the call's start (offline batch generation).

Parameters (a traffic file whose `kind` is "backlog"):

    requests_per_call  requests in each call's backlog
    prompt_len         {"median", "sigma", "min", "max"}: lognormal, clipped
    output_len         the same, for the decode budget (max_new_tokens)
    warmup_max_output  decode budget cap of the warm-up call

The sizes are the lognormal's quantiles at (i + 0.5) / n, not draws. Which
prompt length goes with which budget is one fixed shuffle, and the batch
goes longest output first, so that a call's length is set by its work and
not by where its longest request falls. Sizes and order are the same for
every seed: a seed changes the prompts' token ids and nothing else, so
every run does the same amount of work in the same order.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List, Tuple

import numpy as np

SHUFFLE_SEED = 0


def _quantiles(d: Dict[str, float], n: int) -> List[int]:
    z = NormalDist()
    out = []
    for i in range(n):
        v = d["median"] * math.exp(d["sigma"] * z.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(v), d["min"]), d["max"])))
    return out


def sizes(p: Dict[str, Any]) -> List[Tuple[int, int]]:
    """(prompt_len, max_new_tokens) of one call's backlog, in call order."""
    n = int(p["requests_per_call"])
    prompts = _quantiles(p["prompt_len"], n)
    outputs = _quantiles(p["output_len"], n)
    perm = np.random.default_rng(SHUFFLE_SEED).permutation(n)
    pairs = [(prompts[int(j)], outputs[i]) for i, j in enumerate(perm)]
    return sorted(pairs, key=lambda pr: (-pr[1], -pr[0]))


def max_len(p: Dict[str, Any]) -> int:
    """Cache positions a slot needs: the longest prompt and budget."""
    return int(p["prompt_len"]["max"] + p["output_len"]["max"] + 8)


def token_ids(p: Dict[str, Any], vocab: int, seed: int, call: int
              ) -> List[np.ndarray]:
    """The prompts of call `call` (call -1 is the warm-up)."""
    rng = np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, call + 1])
    return [rng.integers(0, vocab, size=(n,), dtype=np.int32)
            for n, _ in sizes(p)]


def requests(p: Dict[str, Any], vocab: int, seed: int, call: int,
             make_request) -> list:
    """`make_request(rid, prompt, max_new_tokens)` for each request of
    call `call`; the warm-up (call -1) caps the budgets."""
    budgets = [m for _, m in sizes(p)]
    if call < 0:
        budgets = [min(m, int(p["warmup_max_output"])) for m in budgets]
    return [make_request(i, prompt, m) for i, (prompt, m) in
            enumerate(zip(token_ids(p, vocab, seed, call), budgets))]
