"""The serving decode's layer-major cache (DESIGN.md §13), at a tiny size on
the CPU: one batched decode step over a layer-major cache, with a position
per row, against the scalar-position step vmapped over the same caches in
slot-major form; `generate()`'s scalar-position decode against a greedy
full prefill; the fused executor's per-slot commit gate on a layer-major
cache (including `num_layers == n_slots`, where no shape tells the slot
axis from the layer axis); and the slot programs' round trip, prefill rows
-> `sedar_pack_insert` -> `sedar_slot_snapshot` -> `sedar_slot_write`;
and `serve()` of a dense and of a recurrent family, each reporting its
layout in the `serve_start` span. Float32 tiny models; comparisons across
separately compiled programs use written tolerances, copies of untouched
rows are compared bit for bit."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import (RunConfig, SedarConfig, TrainConfig, get_config,
                           reduce_for_smoke)
from repro.configs.base import ModelConfig
from repro.core.engine import sedar_slot_snapshot
from repro.core.injection import InjectionSpec
from repro.models import build_model
from repro.models import transformer as tfm
from repro.runtime.scheduler import synthetic_requests
from repro.runtime.serve import (SedarServer, sedar_pack_insert,
                                 sedar_slot_write)

# dense GQA with QKV bias and fewer KV heads than query heads
GQA = ModelConfig(
    name="tiny-gqa", family="dense", num_layers=2, d_model=64, num_heads=4,
    num_kv_heads=2, head_dim=16, d_ff=96, vocab_size=257, qkv_bias=True,
    tie_embeddings=True, rope_theta=1e4, dtype="float32")
# MLA over a latent cache, a leading dense layer, MoE layers with shared
# experts and a router wider than the experts held here
MLA = ModelConfig(
    name="tiny-mla-moe", family="moe", num_layers=3, d_model=64,
    num_heads=4, num_kv_heads=4, head_dim=24, d_ff=24, vocab_size=257,
    rope_theta=1e4, norm_eps=1e-6, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, num_experts=4, experts_per_token=3,
    router_experts=8, moe_d_ff=24, shared_d_ff=48, moe_raw_topk=True,
    first_dense_layers=1, dense_d_ff=96, dtype="float32")
CFGS = pytest.mark.parametrize("cfg", [GQA, MLA], ids=["gqa", "mla-moe"])


def _rc(cfg, lag=1):
    return RunConfig(model=cfg, train=TrainConfig(),
                     sedar=SedarConfig(validate_lag=lag))


def _random_cache(model, slots, T, seed):
    """A layer-major cache of every slot, every row filled at random."""
    cache, _ = model.init_cache(slots, T, jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(cache))
    return {n: jax.random.normal(k, a.shape, a.dtype)
            for (n, a), k in zip(sorted(cache.items()), keys)}


@CFGS
def test_batched_decode_matches_vmapped_scalar_decode(cfg):
    """Five slots at distinct positions, two of them inactive: the batched
    step's logits match the vmapped scalar-position step's (1e-5 of their
    scale), it writes each slot's rows at that slot's position only (every
    other row bit-identical to the input) and those rows match the
    reference's, and its expert counters leave the inactive slots' routes
    out as the vmapped step's masked sums do."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    N, T = 5, 24
    cache = _random_cache(model, N, T, seed=2)
    pos = jnp.asarray([3, 17, 0, 23, 9], jnp.int32)
    active = jnp.asarray([True, False, True, True, False])
    toks = jnp.asarray([5, 77, 130, 2, 256], jnp.int32)
    kw = {"stats": True} if model.counts_experts else {}
    out = tfm.lm_decode_step(cfg, params, cache, toks, pos,
                             **({**kw, "real": active} if kw else {}))
    slot_major = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0)[:, :, None],
                              cache)                      # (N, L, 1, T, C)
    ref = jax.vmap(lambda c, t, p: tfm.lm_decode_step(
        cfg, params, c, t[None], p, **kw))(slot_major, toks, pos)

    logits, want = out[0], ref[0][:, 0]
    assert float(jnp.max(jnp.abs(logits - want))) <= 1e-5 * float(
        jnp.max(jnp.abs(want)))
    ref_cache = jax.tree.map(lambda a: jnp.moveaxis(a[:, :, 0], 0, 1),
                             ref[1])                      # (L, N, T, C)
    written = np.arange(T)[None, :] == np.asarray(pos)[:, None]   # (N, T)
    for name, new in out[1].items():
        new, old = np.asarray(new), np.asarray(cache[name])
        assert np.array_equal(new[:, ~written], old[:, ~written]), name
        np.testing.assert_allclose(new[:, written],
                                   np.asarray(ref_cache[name])[:, written],
                                   rtol=1e-5, atol=1e-5)
    if kw:
        act = np.asarray(active)
        for k, v in out[2].items():
            per_slot = np.asarray(ref[2][k])
            assert int(v) == int(per_slot.sum() if k == "rows"
                                 else per_slot[act].sum()), k


@CFGS
def test_generate_scalar_position_follows_a_full_prefill(cfg):
    """`generate()` decodes every row at one scalar position: its greedy
    tokens equal those of prefilling the whole sequence again at each
    step."""
    srv = SedarServer(_rc(cfg))
    params = srv.model.init(jax.random.PRNGKey(4))
    prompt = jnp.asarray(np.random.RandomState(5).randint(
        0, cfg.vocab_size, (2, 6)), jnp.int32)
    got, _rep = srv.generate(params, {"tokens": prompt}, steps=5)
    seq = prompt
    for i in range(5):
        logits, _ = tfm.lm_prefill(cfg, params, seq, seq.shape[1],
                                   cache_dtype=jnp.float32)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        assert np.array_equal(np.asarray(got[:, i]), np.asarray(nxt)), i
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)


@pytest.mark.parametrize("layers", [2, 3], ids=["L2", "L3-equals-slots"])
def test_fused_gate_keeps_only_the_faulty_slot_pre_step(layers):
    """A bit flipped in slot 1's logits on replica 1 under the fused
    slotted executor (lag 1, so the in-jit gate bites): slot 1 keeps its
    pre-step token, position and cache (every row below its position bit
    for bit, on both replicas), while slots 0 and 2 commit: their positions
    advance and their rows at the old positions are written. With three
    layers and three slots no shape tells the slot axis from the layer
    axis; the gate is told each entry's axis."""
    cfg = dataclasses.replace(GQA, num_layers=layers)
    spec = InjectionSpec(leaf_idx=1, flat_idx=7, bit=30, replica=1,
                         target="slot", step=2)
    srv = SedarServer(_rc(cfg), backend="fused", inj_spec=spec)
    params = srv.model.init(jax.random.PRNGKey(6))
    N, T = 3, 16
    eng, _ring, _rec = srv._batch_engine(N, T, 1)
    state = {**srv.packed_state(N, T),
             "cache": jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                                   _random_cache(srv.model, N, T, seed=7)),
             "tok": jnp.asarray([[5], [7], [11]], jnp.int32),
             "pos": jnp.asarray([4, 9, 2], jnp.int32),
             "active": jnp.ones((N,), jnp.bool_)}
    dual = eng.executor.init_dual(state)
    for step in range(2):
        dual, eq, _aux = eng.executor._launch(dual, params, step, False,
                                              True)
        assert bool(jnp.all(eq))
    pre = jax.tree.map(np.asarray, dual["s"])   # the launch donates it
    dual, eq, _aux = eng.executor._launch(dual, params, 2,
                                          jnp.asarray(True), True)
    assert np.asarray(eq).tolist() == [True, False, True]
    post = jax.tree.map(np.asarray, dual["s"])
    p = pre["pos"][0]
    assert np.array_equal(post["pos"][:, 1], pre["pos"][:, 1])
    assert np.array_equal(post["tok"][:, 1], pre["tok"][:, 1])
    assert np.array_equal(post["pos"][:, [0, 2]], pre["pos"][:, [0, 2]] + 1)
    for name in ("k", "v"):
        new, old = post["cache"][name], pre["cache"][name]  # (2, L, N, T, C)
        assert np.array_equal(new[:, :, 1, :p[1]], old[:, :, 1, :p[1]])
        assert np.array_equal(new[0], new[1])    # the replicas agree
        for s in (0, 2):
            assert np.array_equal(new[:, :, s, :p[s]], old[:, :, s, :p[s]])
            assert not np.array_equal(new[:, :, s, p[s]], old[:, :, s, p[s]])


def test_pack_insert_snapshot_and_slot_write_round_trip():
    """Prefill rows (slot images (L, 1, T, C) stacked on a pack axis)
    inserted into slots 2 and 0 of a layer-major state come back out of
    the snapshot program as those slots' images, unstacked and stacked
    (fused) alike; writing slot 2's image into slot 3 of an empty state
    gives slot 3 the same image; untouched slots stay empty."""
    srv = SedarServer(_rc(GQA), backend="fused")
    N, T, K = 4, 16, 3
    axes = {k: srv.slot_axes[k] for k in ("cache", "tok", "pos")}
    assert srv.decode_layout == "layer_major" and axes["cache"] == 1
    cache = _random_cache(srv.model, K, T, seed=8)
    rows = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0)[:, :, None], cache)
    toks = jnp.asarray([[4], [8], [15]], jnp.int32)
    poss = jnp.asarray([6, 11, 3], jnp.int32)
    slots, sel = jnp.asarray([2, 0]), jnp.asarray([1, 2])
    st = sedar_pack_insert(srv.packed_state(N, T), slots, sel, rows, toks,
                           poss, cache_axis=1)
    images = sedar_slot_snapshot({k: st[k] for k in axes},
                                 tuple(axes.items()), False)
    dual = srv._batch_engine(N, T, 4)[0].executor.init_dual(st)
    stacked = srv._batch_engine(N, T, 4)[0].executor.slot_images(dual, axes)
    for slot, row in ((2, 1), (0, 2)):
        for img in (images[slot], stacked[slot]):
            for name in cache:
                assert img["cache"][name].shape == (GQA.num_layers, 1, T,
                                                    cache[name].shape[-1])
                assert np.array_equal(np.asarray(img["cache"][name]),
                                      np.asarray(rows[name][row]).astype(
                                          img["cache"][name].dtype))
            assert int(img["tok"][0]) == int(toks[row, 0])
            assert int(img["pos"]) == int(poss[row])
    for slot in (1, 3):
        assert not any(np.asarray(a).any()
                       for a in jax.tree.leaves(images[slot]["cache"]))
    img = images[2]
    st3 = sedar_slot_write(srv.packed_state(N, T), jnp.asarray(3),
                           img["cache"], img["tok"], img["pos"],
                           jnp.asarray(True), cache_axis=1)
    back = sedar_slot_snapshot({k: st3[k] for k in axes},
                               tuple(axes.items()), False)[3]
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(img)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(st3["active"]).tolist() == [False, False, False, True]


@pytest.mark.parametrize("arch,layout", [("qwen2-0.5b", "layer_major"),
                                         ("xlstm-125m", "slot_vmap")])
def test_serve_reports_its_decode_layout(arch, layout):
    """The layout follows the cache the model builds: a dense model's
    layer-stacked cache decodes in one batch, xLSTM's recurrent states
    under the vmap over slots. Either way a fused `serve()` at lag 4
    (flush-edge snapshots, packed or single admissions into the slot axis)
    delivers every budget and the unprotected backend's streams, and its
    `serve_start` span says which layout ran."""
    rc = RunConfig(model=reduce_for_smoke(get_config(arch)),
                   train=TrainConfig(global_batch=2, seq_len=8),
                   sedar=SedarConfig(validate_lag=4))
    streams = {}
    for backend in ("none", "fused"):
        srv = SedarServer(rc, backend=backend)
        params = srv.model.init(jax.random.PRNGKey(0))
        tr = obs.enable_trace()
        try:
            reqs, _rep = srv.serve(params, synthetic_requests(
                3, arrival_rate=2.0, prompt_lengths=(4, 8),
                max_new_choices=(4, 6), seed=1), slots=2)
            start = [e for e in tr.events if e["name"] == "serve_start"]
        finally:
            obs.disable_trace()
        assert srv.decode_layout == layout
        assert start[0]["args"]["decode_layout"] == layout
        assert all(r.status == "done" and len(r.tokens) == r.max_new_tokens
                   for r in reqs)
        streams[backend] = [list(r.tokens) for r in reqs]
    assert streams["fused"] == streams["none"]
