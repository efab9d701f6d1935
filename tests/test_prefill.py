"""Bucketed packed protected prefill + AOT compile cache (DESIGN.md §14).

The two oracles: (1) bucketed/packed admission must be BITWISE equivalent
to the exact-shape per-request path — right-padding and packing are layout
transforms, not math changes; (2) the per-prompt detection contract — a
fault in one pack row's prefill retries/rejects ONLY that request, and the
survivors' streams equal the fault-free run. Plus the compile-accounting
property: after `warmup()` the traffic loop never compiles.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, TrainConfig, get_config, \
    reduce_for_smoke
from repro.core import hostsync
from repro.core.injection import InjectionSpec
from repro.runtime.prefill import (BucketedPrefill, bucket_for, count_compiles,
                                   group_packs, make_buckets, pack_for,
                                   pack_sizes)
from repro.runtime.scheduler import Request, ttft_percentiles_ms
from repro.runtime.serve import SedarServer

SLOTS = 3


def _cfg():
    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    return RunConfig(model=cfg, train=TrainConfig(global_batch=2, seq_len=8))


def _reqs():
    """Three t=0 arrivals spanning two buckets: lens 4, 6 -> bucket 8
    (one pack of 2), len 9 -> bucket 16 (pack of 1)."""
    return [Request(rid=i, prompt=np.arange(1, ln + 1, dtype=np.int32),
                    max_new_tokens=4, arrival=0)
            for i, ln in enumerate((4, 6, 9))]


def _row1_spec(**kw):
    """Transient SDC in pack row 1's prefill logits on replica 1 — hits the
    bucket-8 pack's second prompt (rid 1) at the t=0 admission."""
    kw.setdefault("target", "prefill")
    return InjectionSpec(leaf_idx=1, flat_idx=7, bit=30, step=0, replica=1,
                         **kw)


@pytest.fixture(scope="module")
def setup():
    rc = _cfg()
    srv = SedarServer(rc, dual=True)
    params = srv.model.init(jax.random.PRNGKey(0))
    clean, rep = srv.serve(params, _reqs(), slots=SLOTS)
    assert not rep.detections and rep.prefill_packs == 2
    return rc, params, {r.rid: list(r.tokens) for r in clean}


def _assert_streams_equal(out, clean_toks):
    for rid, r in out.items():
        assert list(r.tokens) == clean_toks[rid], f"request {rid} diverged"


# ---------------------------------------------------------------------------
# bucket / pack geometry
# ---------------------------------------------------------------------------

def test_bucket_ladder_geometry():
    assert make_buckets(100) == (8, 16, 32, 64, 128)
    assert make_buckets(8) == (8,)
    assert bucket_for(8, (8, 16)) == 8
    assert bucket_for(9, (8, 16)) == 16
    assert bucket_for(17, (8, 16)) is None       # overflow -> legacy path
    assert pack_sizes(4) == (1, 2, 4)
    assert pack_for(3, 4) == 4
    assert pack_for(1, 4) == 1
    with pytest.raises(ValueError):
        pack_for(5, 4)


def test_group_packs_by_bucket_and_chunk():
    items = list("abcdef")
    lengths = [4, 6, 9, 8, 5, 40]
    packs, overflow = group_packs(items, lengths, (8, 16), max_pack=2)
    assert overflow == ["f"]                     # 40 > largest bucket
    assert packs == [(8, ["a", "b"]), (8, ["d", "e"]), (16, ["c"])]


# ---------------------------------------------------------------------------
# bitwise equivalence of the padded / packed transforms
# ---------------------------------------------------------------------------

def test_padded_prefill_bitwise_equals_exact(setup):
    rc, params, _ = setup
    srv = SedarServer(rc, dual=True)
    toks = jnp.asarray(np.arange(1, 6, dtype=np.int32))[None, :]   # S=5
    max_len = 24
    exact_logits, _ = srv.model.prefill(params, {"tokens": toks}, max_len)
    padded = srv.prefiller.prefill_padded(params, toks, max_len)
    assert padded is not None
    np.testing.assert_array_equal(np.asarray(padded[0]),
                                  np.asarray(exact_logits))


def test_packed_serve_equals_legacy_admission(setup):
    """The whole point: packed bucketed admission produces bitwise the same
    streams as one-exact-launch-per-request admission (forced via a ladder
    every prompt overflows)."""
    rc, params, clean_toks = setup
    srv = SedarServer(rc, dual=True, prefill_buckets=(1,))
    out, rep = srv.serve(params, _reqs(), slots=SLOTS)
    assert rep.prefill_packs == 0
    _assert_streams_equal({r.rid: r for r in out}, clean_toks)


def test_generate_reuses_bucketed_prefill(setup):
    """generate() rides the same bucket ladder: same-bucket prompt lengths
    share ONE compiled program, and the streams equal the legacy
    exact-shape prefill (forced via a ladder every prompt overflows)."""
    rc, params, _ = setup
    srv = SedarServer(rc, dual=True)
    srv_legacy = SedarServer(rc, dual=True, prefill_buckets=(1,))
    max_len = 32
    for S in (5, 7):                             # both -> bucket 8
        prompt = {"tokens": jnp.asarray(
            np.arange(1, S + 1, dtype=np.int32))[None, :]}
        toks, _ = srv.generate(params, prompt, steps=4, max_len=max_len)
        ref, _ = srv_legacy.generate(params, prompt, steps=4,
                                     max_len=max_len)
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref))
    with count_compiles() as st:
        prompt = {"tokens": jnp.asarray(
            np.arange(2, 8, dtype=np.int32))[None, :]}       # S=6, bucket 8
        srv.generate(params, prompt, steps=4, max_len=max_len)
    assert st.compiles == 0, st.by_key


# ---------------------------------------------------------------------------
# AOT compile cache
# ---------------------------------------------------------------------------

def test_one_compile_per_bucket_pack_shape(setup):
    """Regression: repeated same-shape launches hit the cache; the compile
    count is exactly one per (kind, bucket, K) key."""
    rc, params, _ = setup
    pf = BucketedPrefill(SedarServer(rc, dual=True).model,
                         backend="sequential", max_pack=4)
    max_len = 24
    prompts2 = [np.arange(1, 5, dtype=np.int32)] * 2
    with count_compiles() as st:
        for _ in range(3):
            pf.protected_pack(params, prompts2, max_len, 0)      # K=2
        pf.protected_pack(params, prompts2 * 2, max_len, 1)      # K=4
        pf.prefill_padded(params, jnp.asarray(prompts2[0])[None, :],
                          max_len)
    assert st.compiles == 3, st.by_key
    assert all(v == 1 for v in st.by_key.values()), st.by_key


def test_warmup_kills_traffic_time_compiles(setup):
    """The acceptance property: after warmup() the ENTIRE serve loop —
    packed admission at every bucket/pack shape plus decode — performs
    zero prefill-program compiles."""
    rc, params, clean_toks = setup
    srv = SedarServer(rc, dual=True)
    reqs = _reqs()
    max_len = (max(r.prompt_len for r in reqs)
               + max(r.max_new_tokens for r in reqs) + 8)
    n = srv.warmup_prefill(params, max_len)
    assert n == len(srv.prefiller.usable_buckets(max_len)) * (1 + 3)
    with count_compiles() as st:
        out, rep = srv.serve(params, reqs, slots=SLOTS, max_len=max_len)
    assert rep.prefill_packs == 2
    assert st.compiles == 0, st.by_key
    _assert_streams_equal({r.rid: r for r in out}, clean_toks)


def test_admission_readback_is_one_batch_per_pack(setup):
    """Host-sync accounting: the pack's tokens AND verdicts come back in
    ONE batched transfer (2 items) per launch — not per request."""
    rc, params, _ = setup
    srv = SedarServer(rc, dual=True)
    srv.serve(params, _reqs(), slots=SLOTS)      # warm jits
    with hostsync.count_transfers() as st:
        out, rep = srv.serve(params, _reqs(), slots=SLOTS, validate_lag=8)
    assert rep.prefill_packs == 2
    assert st.by_label["prefill_emit"] == 2 * rep.prefill_packs
    tt50, tt99 = ttft_percentiles_ms(out)
    assert 0 < tt50 <= tt99                      # TTFT stamps functional


# ---------------------------------------------------------------------------
# per-prompt detection contract
# ---------------------------------------------------------------------------

def test_transient_pack_fault_retries_only_that_row(setup):
    """A transient SDC in pack row 1 is caught by the lane compare; only
    rid 1 is retried (the rest of the pack admits first pass) and every
    stream equals the fault-free run."""
    rc, params, clean_toks = setup
    srv = SedarServer(rc, dual=True, inj_spec=_row1_spec())
    out, rep = srv.serve(params, _reqs(), slots=SLOTS)
    out = {r.rid: r for r in out}
    assert all(r.status == "done" for r in out.values())
    assert rep.prefill_retries == 1
    tdc = [e for e in rep.detections if e.boundary == "prefill"]
    assert len(tdc) == 1 and tdc[0].detail["rids"] == [1]
    _assert_streams_equal(out, clean_toks)


def test_fused_backend_pack_fault_equality(setup):
    """Same contract on the fused backend (lanes from the same compiled
    executable run twice): row-localized retry, clean-run streams."""
    rc, params, clean_toks = setup
    srv = SedarServer(rc, backend="fused", inj_spec=_row1_spec())
    out, rep = srv.serve(params, _reqs(), slots=SLOTS)
    out = {r.rid: r for r in out}
    assert all(r.status == "done" for r in out.values())
    assert rep.prefill_retries == 1
    _assert_streams_equal(out, clean_toks)


def test_persistent_pack_fault_rejects_only_that_request(setup):
    """A stuck lane (persistent=True): retries RELAUNCH the original pack
    shape so the fault keeps hitting the same occupant, the budget
    exhausts, and ONLY rid 1 is rejected — the pack's other rows and the
    other pack complete with clean streams."""
    rc, params, clean_toks = setup
    notified = []
    srv = SedarServer(rc, dual=True, max_retries=3,
                      inj_spec=_row1_spec(persistent=True))
    out, rep = srv.serve(params, _reqs(), slots=SLOTS,
                         notify_reject=lambda r, e: notified.append(r.rid))
    out = {r.rid: r for r in out}
    assert rep.rejected == [1] == notified
    assert out[1].status == "rejected"
    assert "prefill validation" in out[1].reject_reason
    for rid in (0, 2):
        assert out[rid].status == "done"
        assert list(out[rid].tokens) == clean_toks[rid]


def test_abft_pack_forward_corrects_and_admits(setup):
    """Replica-free backend: a single-element fault in the packed-prefill
    checksum window is forward-corrected — every row admits FIRST pass
    (verdict CORRECTED, zero retries), the detection is recorded, and the
    streams equal the dual-replica clean run."""
    rc, params, clean_toks = setup
    spec = InjectionSpec(leaf_idx=0, flat_idx=5, bit=30, step=0, replica=0,
                         target="prefill_kernel")
    srv = SedarServer(rc, backend="abft", inj_spec=spec)
    out, rep = srv.serve(params, _reqs(), slots=SLOTS)
    out = {r.rid: r for r in out}
    assert all(r.status == "done" for r in out.values())
    assert rep.prefill_retries == 0
    corrected = [e for e in rep.detections if e.effect == "abft_corrected"]
    assert len(corrected) == 1 and corrected[0].boundary == "prefill"
    _assert_streams_equal(out, clean_toks)


def test_abft_pack_uncorrectable_localizes_rows(setup):
    """Multi-element corruption defeats single-element correction: the
    violated row residuals localize the bad rows, only those retry, and
    the re-execution (fault disarmed) converges to the clean run."""
    rc, params, clean_toks = setup
    spec = InjectionSpec(leaf_idx=0, flat_idx=5, bit=30, step=0, replica=0,
                         target="prefill_kernel", n_elems=2)
    srv = SedarServer(rc, backend="abft", inj_spec=spec)
    out, rep = srv.serve(params, _reqs(), slots=SLOTS)
    out = {r.rid: r for r in out}
    assert all(r.status == "done" for r in out.values())
    assert rep.prefill_retries >= 1
    tdc = [e for e in rep.detections if e.boundary == "prefill"
           and e.effect == "TDC"]
    assert tdc and len(tdc[0].detail["rids"]) < len(_reqs())   # localized
    _assert_streams_equal(out, clean_toks)


def test_hybrid_backend_clean_packed_serve(setup):
    """The checksum-guarded pack path also serves the hybrid backend, and
    its clean streams equal the dual-replica run."""
    rc, params, clean_toks = setup
    srv = SedarServer(rc, backend="hybrid")
    out, rep = srv.serve(params, _reqs(), slots=SLOTS)
    assert not rep.detections and rep.prefill_packs == 2
    _assert_streams_equal({r.rid: r for r in out}, clean_toks)
