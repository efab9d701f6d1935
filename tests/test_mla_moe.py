"""Latent attention (MLA), YaRN rope and the dropless held-expert MoE of the
DeepSeek-V2 block, at a tiny size on the CPU: the absorbed decode form
against the expanded form over one latent cache, right-padded packed
prefill against exact prefill, YaRN's tables against its formula, the MoE
against a per-token loop, both `fused` replicas routing alike, a planted
slot fault detected and rolled back, and the expert counters against counts
made by hand. Comparisons across separately compiled programs use written
tolerances, never bitwise equality."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, SedarConfig, TrainConfig
from repro.configs.base import ModelConfig
from repro.core.injection import InjectionSpec
from repro.models import build_model
from repro.models import layers as nn
from repro.models import moe as moe_lib
from repro.models import transformer as tfm
from repro.runtime.scheduler import Request
from repro.runtime.serve import SedarServer

# the published YaRN settings of DeepSeek-V2-Lite
YARN = (("factor", 40.0), ("original_max_position_embeddings", 4096.0),
        ("beta_fast", 32.0), ("beta_slow", 1.0), ("mscale", 0.707),
        ("mscale_all_dim", 0.707))
TINY = ModelConfig(
    name="tiny-mla-moe", family="moe", num_layers=3, d_model=64,
    num_heads=4, num_kv_heads=4, head_dim=24, d_ff=24, vocab_size=257,
    rope_theta=1e4, norm_eps=1e-6,
    rope_scaling=tuple((k, 64.0 if k.startswith("original") else v)
                       for k, v in YARN),
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_experts=4, experts_per_token=3, router_experts=8, moe_d_ff=24,
    shared_d_ff=48, moe_raw_topk=True, first_dense_layers=1, dense_d_ff=96,
    dtype="float32")


@pytest.fixture(scope="module")
def params():
    return build_model(TINY).init(jax.random.PRNGKey(3))


def _tokens(n, seed=0, batch=1):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, TINY.vocab_size, (batch, n)), jnp.int32)


def test_yarn_tables_follow_the_formula():
    """At the published values YaRN blends rope pairs 10-23 (pairs below
    keep theta^(-2i/d), pairs above divide it by 40), the sin/cos magnitude
    is 1 and the softmax scale 192^-1/2 (1 + 0.1 * 0.707 * ln 40)^2."""
    s = dict(YARN)
    assert nn.yarn_ramp(64, 1e4, s) == (10, 23)
    freq, mscale = nn.yarn_frequencies(64, 1e4, s)
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(np.asarray(freq),
                               base * (1 - ramp) + base / 40 * ramp,
                               rtol=1e-6)
    assert np.allclose(np.asarray(freq)[:11], base[:11], rtol=1e-6)
    assert np.allclose(np.asarray(freq)[23:], base[23:] / 40, rtol=1e-6)
    assert mscale == 1.0
    cfg = dataclasses.replace(TINY, qk_nope_head_dim=128,
                              qk_rope_head_dim=64, rope_scaling=YARN)
    want = 192 ** -0.5 * (1 + 0.1 * 0.707 * math.log(40)) ** 2
    assert nn.attention_scale(cfg) == pytest.approx(want, rel=1e-12)
    assert round(nn.attention_scale(cfg), 5) == 0.11472
    # tables: angle = position * frequency
    sin, cos = nn.rope_tables(jnp.asarray([3]), 64, 1e4, YARN)
    np.testing.assert_allclose(np.asarray(sin[0]), np.sin(3 * freq),
                               rtol=1e-6)


def test_absorbed_decode_attention_matches_expanded(params):
    """Over one latent cache, the decode form (W_uk folded into the query,
    W_uv into the output) equals the prefill form (latent up-projected into
    per-head keys and values) at the last position, float32: tolerance
    1e-5 of the output's scale, reassociation of float32 sums."""
    ap = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    S = 11
    x = jax.random.normal(jax.random.PRNGKey(5), (1, S, TINY.d_model))
    sin, cos = tfm._rope(TINY, jnp.arange(S))
    q_nope, q_pe = nn.mla_query(TINY, ap, x, sin, cos)
    rows = nn.mla_latent(TINY, ap, x, sin, cos)
    q, k, v = nn.mla_expand(ap, q_nope, q_pe, rows)
    want = nn.causal_attention(q, k, v, scale=nn.attention_scale(TINY))
    T = 16   # cache positions beyond S - 1 hold garbage the mask hides
    pad = lambda a: jnp.concatenate(  # noqa: E731
        [a, 7.0 * jnp.ones((1, T - S) + a.shape[2:])], axis=1)
    got = nn.mla_absorbed_attention(
        ap, q_nope[:, -1:], q_pe[:, -1:], pad(rows["c_kv"]),
        pad(rows["k_pe"]), S - 1, nn.attention_scale(TINY))
    err = float(jnp.max(jnp.abs(got[:, 0] - want[:, -1])))
    assert err <= 1e-5 * float(jnp.max(jnp.abs(want)))


def test_chunked_attention_takes_narrower_values_and_a_scale():
    """Prompts over CHUNKED_THRESHOLD attend through the chunked path: MLA's
    values are narrower than its keys and its scale is YaRN's. Float32,
    against the exact path, to 1e-5 of the output's scale."""
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (2, 20, 4, 24))
    k = jax.random.normal(ks[1], (2, 20, 4, 24))
    v = jax.random.normal(ks[2], (2, 20, 4, 16))
    want = nn.causal_attention(q, k, v, scale=0.3)
    got = nn.chunked_causal_attention(q, k, v, q_chunk=8, k_chunk=8,
                                      scale=0.3)
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * float(
        jnp.max(jnp.abs(want)))


def test_packed_right_padded_prefill_matches_exact(params):
    """Right-padded rows of a pack give the logits and latent cache rows of
    each prompt's exact prefill: causal attention keeps pads out of real
    positions and the dropless MoE lets no pad take a real token's place.
    float32, tolerance 1e-5 of the logits' scale (different shapes compile
    to different reductions)."""
    lens = [5, 9, 16]
    toks = _tokens(16, seed=1, batch=3)
    padded = jnp.where(jnp.arange(16)[None] < jnp.asarray(lens)[:, None],
                       toks, 0)
    lg, cache = tfm.lm_prefill(TINY, params, padded, 24,
                               lengths=jnp.asarray(lens),
                               cache_dtype=jnp.float32)
    for i, n in enumerate(lens):
        lg1, c1 = tfm.lm_prefill(TINY, params, toks[i:i + 1, :n], 24,
                                 cache_dtype=jnp.float32)
        scale = float(jnp.max(jnp.abs(lg1)))
        assert float(jnp.max(jnp.abs(lg[i] - lg1[0]))) <= 1e-5 * scale
        for name in ("c_kv", "k_pe"):
            np.testing.assert_allclose(np.asarray(cache[name][:, i, :n]),
                                       np.asarray(c1[name][:, 0, :n]),
                                       atol=1e-5)


def test_dropless_moe_matches_a_per_token_loop():
    """Every token sent to one expert overflows any capacity: the one-device
    layer drops none. A configuration with every expert held and the top-k
    renormalised (phi3.5 and dbrx keep it) equals a loop over tokens and
    their experts, float32, to 1e-5 of the output's scale."""
    cfg = dataclasses.replace(TINY, num_experts=4, router_experts=0,
                              experts_per_token=2, moe_raw_topk=False,
                              shared_d_ff=0)
    p, _ = moe_lib.init_moe(jax.random.PRNGKey(2), cfg)
    p["router"] = p["router"].at[:, 1].add(5.0)   # skew toward expert 1
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, cfg.d_model))
    out, aux = moe_lib.moe_mlp(cfg, p, x)
    xt = np.asarray(x.reshape(-1, cfg.d_model), np.float64)
    want = np.zeros_like(xt)
    probs = jax.nn.softmax(jnp.asarray(xt, jnp.float32) @ p["router"], -1)
    for t in range(xt.shape[0]):
        top = np.argsort(-np.asarray(probs[t]))[:2]
        w = np.asarray(probs[t])[top] / np.sum(np.asarray(probs[t])[top])
        for e, we in zip(top, w):
            g = xt[t] @ np.asarray(p["w_gate"][e])
            u = xt[t] @ np.asarray(p["w_up"][e])
            want[t] += we * ((g / (1 + np.exp(-g))) * u) @ np.asarray(
                p["w_down"][e])
    assert int(jnp.sum(aux["moe_held"])) == xt.shape[0] * 2
    err = np.max(np.abs(np.asarray(out).reshape(xt.shape) - want))
    assert err <= 1e-5 * np.max(np.abs(want))


def test_prefill_counters_split_over_shares_and_skip_pads():
    """With the MoE layer last, routing does not depend on which experts are
    held: the held routes of two halves of the router add up to every route
    of the real tokens, pads excluded; rows count every position."""
    cfg = dataclasses.replace(TINY, first_dense_layers=2)
    lens = jnp.asarray([7, 12])
    toks = _tokens(12, seed=2, batch=2)
    held = []
    for offset in (0, 4):
        c = dataclasses.replace(cfg, expert_offset=offset)
        p = build_model(c).init(jax.random.PRNGKey(3))
        _, _, st = tfm.lm_prefill(c, p, toks, 16, lengths=lens, stats=True)
        assert int(st["routes"]) == 3 * 19            # k x real tokens
        assert int(st["rows"]) == 2 * 12 * 4          # B x S x held
        held.append(int(st["routes_held"]))
    assert held[0] + held[1] == 3 * 19 and 0 < held[0] < 3 * 19


def _rc(cfg=TINY, lag=4):
    return RunConfig(model=dataclasses.replace(cfg, dtype="bfloat16"),
                     train=TrainConfig(), sedar=SedarConfig(validate_lag=lag))


def _requests():
    rng = np.random.RandomState(7)
    return [Request(rid=i, prompt=rng.randint(0, 257, n).astype(np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(5, 6), (12, 3), (14, 9), (9, 4)])]


def test_serve_counters_match_a_count_by_hand():
    """Routes: k x MoE layers x real tokens (prompts at admission, every
    decoded token after its first); rows: held experts x MoE layers x every
    position of every pack row, and of every slot at every decode tick.
    With every expert held, every route lands here."""
    cfg = dataclasses.replace(TINY, router_experts=0)
    srv = SedarServer(_rc(cfg), backend="fused", prefill_buckets=(8, 16),
                      max_pack=2)
    params = srv.model.init(jax.random.PRNGKey(1))
    out, rep = srv.serve(params, _requests(), slots=2, max_len=32,
                         validate_lag=4)
    assert [len(r.tokens) for r in out] == [6, 3, 9, 4]
    k_l, e_l = 3 * 2, 4 * 2            # per token: routes, held-expert rows
    c = rep.expert_counts
    assert c["prefill"]["routes"] == k_l * (5 + 12 + 14 + 9)
    assert c["decode"]["routes"] == k_l * (5 + 2 + 8 + 3)
    assert c["prefill"]["routes_held"] == c["prefill"]["routes"]
    assert c["decode"]["routes_held"] == c["decode"]["routes"]
    assert c["decode"]["rows"] == e_l * 2 * rep.steps
    # packs, by admission: {5}, {12, 14} share bucket 16, {9}; pack sizes
    # are powers of two
    assert c["prefill"]["rows"] == e_l * (8 * 1 + 16 * 2 + 16 * 1)


def test_fused_replicas_route_alike_and_a_slot_fault_rolls_back():
    """Both replicas of the fused step route the same tokens to the same
    experts (equal counters and equal latent caches, from one compiled
    program); a bit flipped in one slot's logits on replica 1 is detected
    at the flush and only that slot rolls back from the Tier-0 ring."""
    rc = _rc()
    srv = SedarServer(rc, backend="fused", prefill_buckets=(8, 16),
                      max_pack=2)
    params = srv.model.init(jax.random.PRNGKey(1))
    eng, _ring, _rec = srv._batch_engine(2, 32, 4)
    state = {**srv.packed_state(2, 32),
             "tok": jnp.asarray([[3], [9]], jnp.int32),
             "active": jnp.asarray([True, True])}
    dual = eng.executor.init_dual(state)
    for step in range(3):
        dual, eq, _aux = eng.executor._launch(dual, params, step, False, True)
        assert bool(jnp.all(eq))
    s = dual["s"]
    assert int(s["moe"]["routes_held"][0]) > 0
    for leaf in jax.tree.leaves({"moe": s["moe"], "cache": s["cache"],
                                 "tok": s["tok"]}):
        assert bool(jnp.all(leaf[0] == leaf[1]))

    spec = InjectionSpec(leaf_idx=1, flat_idx=7, bit=14, step=5, replica=1,
                         target="slot")
    faulty = SedarServer(rc, backend="fused", inj_spec=spec,
                         prefill_buckets=(8, 16), max_pack=2)
    out, rep = faulty.serve(params, _requests(), slots=2, max_len=32,
                            validate_lag=4)
    assert len(rep.detections) == 1
    ev = rep.detections[0]
    assert ev.boundary == "deferred" and ev.detail["slots"] == [1]
    assert rep.rollbacks == 1
    assert all(r.status == "done" and len(r.tokens) == r.max_new_tokens
               for r in out)
