"""Continuous-batching protected serving (DESIGN.md §13): per-slot
detection, per-request recovery, zero-sync hot path, backend equality.

The recurring oracle: a fault campaign's token streams must be bitwise
identical to the fault-free run — for UNAFFECTED requests because their
slots are never touched, and for the AFFECTED request because transient
faults are repaired (per-slot retry or Tier-0 ring rollback) before its
stream completes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import count_disk_reads
from repro.configs import RunConfig, TrainConfig, get_config, \
    reduce_for_smoke
from repro.core import hostsync
from repro.core.engine import slot_image
from repro.core.injection import InjectionSpec
from repro.runtime.scheduler import synthetic_requests
from repro.runtime.serve import SedarServer

SLOTS = 3
FAULT_SLOT = 1
FAULT_STEP = 3


def _cfg():
    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    return RunConfig(model=cfg, train=TrainConfig(global_batch=2, seq_len=8))


def _requests():
    return synthetic_requests(5, arrival_rate=2.0, prompt_lengths=(4, 8),
                              max_new_choices=(4, 8), seed=1)


def _long_requests():
    """Budgets long enough that lag-4 and lag-8 flush edges fall while
    several slots run (the Tier-0 flush snapshots carry the rollbacks)."""
    return synthetic_requests(5, arrival_rate=2.0, prompt_lengths=(4, 8),
                              max_new_choices=(16, 24), seed=1)


def _serve(srv, params, **kw):
    reqs, rep = srv.serve(params, _requests(), slots=SLOTS, **kw)
    return {r.rid: r for r in reqs}, rep


def _slot_spec(**kw):
    """Transient SDC localized to FAULT_SLOT's logits on replica 1."""
    kw.setdefault("target", "slot")
    kw.setdefault("step", FAULT_STEP)
    return InjectionSpec(leaf_idx=FAULT_SLOT, flat_idx=7, bit=30,
                         replica=1, **kw)


@pytest.fixture(scope="module")
def setup():
    rc = _cfg()
    srv = SedarServer(rc, dual=True)
    params = srv.model.init(jax.random.PRNGKey(0))
    clean, rep = _serve(srv, params)
    assert not rep.detections
    return rc, params, {rid: list(r.tokens) for rid, r in clean.items()}


def _assert_streams_equal(out, clean_toks):
    for rid, r in out.items():
        assert list(r.tokens) == clean_toks[rid], f"request {rid} diverged"


# ---------------------------------------------------------------------------
# clean-path semantics
# ---------------------------------------------------------------------------

def test_clean_run_completes_all(setup):
    rc, params, clean_toks = setup
    srv = SedarServer(rc, dual=True)
    out, rep = _serve(srv, params)
    assert all(r.status == "done" for r in out.values())
    assert all(len(r.tokens) == r.max_new_tokens for r in out.values())
    assert sorted(rep.completed) == sorted(out)
    assert rep.tokens_emitted == sum(r.max_new_tokens for r in out.values())


def test_slot_count_invariance(setup):
    """A request's stream depends on its prompt and the params only — NOT
    on which slot it lands in or how many slots the server packs."""
    rc, params, clean_toks = setup
    srv = SedarServer(rc, dual=True)
    reqs, _ = srv.serve(params, _requests(), slots=2)
    _assert_streams_equal({r.rid: r for r in reqs}, clean_toks)


def test_matches_generate_oracle(setup):
    """Continuous per-request decode equals the synchronous generate() loop
    on the same prompt (same math, packed vs whole-batch)."""
    rc, params, clean_toks = setup
    srv = SedarServer(rc, dual=True)
    reqs = synthetic_requests(2, arrival_rate=5.0, prompt_lengths=(6,),
                              max_new_choices=(5,), seed=3)
    out, _ = srv.serve(params, reqs, slots=2)
    for r in out:
        toks, _ = srv.generate(
            params, {"tokens": jnp.asarray(r.prompt[None, :])},
            steps=r.max_new_tokens, max_len=6 + 5 + 8)
        assert list(r.tokens) == list(np.asarray(toks)[0])


def test_backpressure_sheds_load(setup):
    rc, params, _ = setup
    srv = SedarServer(rc, dual=True)
    reqs = synthetic_requests(6, arrival_rate=100.0, seed=2)  # all at t=0
    out, rep = srv.serve(params, reqs, slots=2, queue_depth=2)
    rejected = [r for r in out if r.status == "rejected"]
    assert rejected and all(r.reject_reason == "backpressure"
                            for r in rejected)
    assert sorted(rep.rejected) == sorted(r.rid for r in rejected)
    assert all(r.status == "done" for r in out if r.rid not in rep.rejected)


# ---------------------------------------------------------------------------
# per-slot fault localization + recovery
# ---------------------------------------------------------------------------

def test_slot_fault_partial_commit_retry(setup):
    """Immediate mode (lag=1): a slot-localized SDC is detected at the
    commit gate, PARTIALLY committed (detail.slots names the slot), the
    faulty slot re-executes, and every stream equals the fault-free run."""
    rc, params, clean_toks = setup
    srv = SedarServer(rc, dual=True, inj_spec=_slot_spec())
    out, rep = _serve(srv, params)
    assert len(rep.detections) == 1
    ev = rep.detections[0]
    assert ev.boundary == "commit" and ev.step == FAULT_STEP
    assert ev.detail["slots"] == [FAULT_SLOT] and ev.detail["partial"]
    assert rep.retries >= 1 and rep.rollbacks == 0
    assert all(r.status == "done" for r in out.values())
    _assert_streams_equal(out, clean_toks)


def test_slot_fault_deferred_ring_rollback(setup):
    """Deferred mode (lag=4): the corrupted commit lands optimistically,
    the window flush localizes the slot AND the step, only that slot rolls
    back from the Tier-0 ring (tokens truncated + re-decoded), and every
    stream still equals the fault-free run."""
    rc, params, clean_toks = setup
    srv = SedarServer(rc, dual=True, inj_spec=_slot_spec())
    out, rep = _serve(srv, params, validate_lag=4)
    assert len(rep.detections) == 1
    ev = rep.detections[0]
    assert ev.boundary == "deferred" and ev.step == FAULT_STEP
    assert ev.detail["slots"] == [FAULT_SLOT]
    assert ev.detail["slot_first_bad"] == {FAULT_SLOT: FAULT_STEP}
    assert ev.detail["detected_at"] <= FAULT_STEP + 4
    assert rep.rollbacks == 1 and rep.truncated_tokens > 0
    assert all(r.status == "done" for r in out.values())
    _assert_streams_equal(out, clean_toks)
    # exactly ONE request (the faulty slot's tenant) was truncated/re-decoded
    assert sum(1 for r in out.values() if r.truncated_tokens > 0) == 1


def test_fault_fires_across_idle_ticks(setup):
    """Sparse traffic: idle ticks (no active slot) advance BOTH the driver
    clock and the device decode tick, so a fault scheduled after an idle
    gap still fires (regression: the clocks used to drift and the engine's
    once-only flag disarmed the spec before the device reached its step)."""
    rc, params, _ = setup
    from repro.runtime.scheduler import Request

    def reqs():
        return [Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                        max_new_tokens=3, arrival=0),
                Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                        max_new_tokens=4, arrival=8)]

    # request 0 finishes around tick 2; ticks ~3-7 are idle; the fault
    # lands on request 1's decode stream after the gap
    spec = InjectionSpec(leaf_idx=0, flat_idx=7, bit=30, step=9, replica=1,
                        target="slot")
    srv_c = SedarServer(rc, dual=True)
    clean, _ = srv_c.serve(params, reqs(), slots=1)
    srv = SedarServer(rc, dual=True, inj_spec=spec)
    out, rep = srv.serve(params, reqs(), slots=1)
    assert len(rep.detections) == 1 and rep.detections[0].step == 9
    for r, c in zip(out, clean):
        assert list(r.tokens) == list(c.tokens)


def test_whole_batch_fault_retries_all_active(setup):
    """A params-target fault corrupts EVERY active slot's logits: the event
    names all of them and re-execution still converges to the clean run."""
    rc, params, clean_toks = setup
    spec = InjectionSpec(leaf_idx=2, flat_idx=3, bit=30, step=FAULT_STEP,
                         replica=1, target="params")
    srv = SedarServer(rc, dual=True, inj_spec=spec)
    out, rep = _serve(srv, params)
    assert rep.detections and len(rep.detections[0].detail["slots"]) > 1
    _assert_streams_equal(out, clean_toks)


def test_persistent_fault_rejects_only_that_request(setup):
    """A stuck bit in one slot (persistent=True re-injects on every step):
    the consecutive per-slot budget exhausts, THAT request is rejected
    (per-request L1 safe stop with notification) and the server keeps
    serving — everyone else completes with clean streams."""
    rc, params, clean_toks = setup
    notified = []
    srv = SedarServer(rc, dual=True, max_retries=3,
                      inj_spec=_slot_spec(persistent=True))
    out, rep = _serve(srv, params, notify_reject=lambda r, e:
                      notified.append(r.rid))
    rejected = [r for r in out.values() if r.status == "rejected"]
    assert len(rejected) == 1
    assert "safe stop" in rejected[0].reject_reason
    assert rep.rejected == [rejected[0].rid] == notified
    assert not rep.stopped          # the SERVER never dies
    for rid, r in out.items():
        if r.status == "done":
            assert list(r.tokens) == clean_toks[rid]


def test_rejection_resets_slot_budget_for_next_tenant():
    """The consecutive budget is per REQUEST: after a rejection the next
    tenant admitted into the same slot starts with a clean count, not the
    exhausted one (regression: the counter used to survive the eviction)."""
    from repro.checkpoint.tiers import SlotRing
    from repro.core.detection import DetectionEvent
    from repro.core.recovery import SlotRecovery

    rec = SlotRecovery(SlotRing(), max_retries=2)

    def ev():
        return DetectionEvent(step=1, boundary="commit", effect="TDC",
                              detail={"slots": [0], "partial": True})

    for _ in range(3):
        rec.on_detection(ev())
    assert rec.take_rejections() == [0]
    # next tenant's FIRST failure must be a retry, not a rejection
    action = rec.on_detection(ev())
    assert action.kind == "retry" and action.rollbacks == 1
    assert rec.take_rejections() == []


def test_single_token_budget_delivers_exactly_one(setup):
    """max_new_tokens=1 is satisfied by the prefill token alone: the slot
    must release at admission, not decode (and emit) a second token."""
    rc, params, _ = setup
    from repro.runtime.scheduler import Request
    srv = SedarServer(rc, dual=True)
    reqs = [Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=1, arrival=0),
            Request(rid=1, prompt=np.arange(6, dtype=np.int32),
                    max_new_tokens=3, arrival=0)]
    out, rep = srv.serve(params, reqs, slots=2)
    assert all(r.status == "done" for r in out)
    assert [len(r.tokens) for r in out] == [1, 3]
    assert rep.tokens_emitted == 4


# ---------------------------------------------------------------------------
# zero-sync / zero-disk hot path (acceptance property)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["sequential", "fused"])
def test_fault_free_deferred_path_is_sync_and_disk_free(setup, backend):
    """With validate_lag >= 8 the fault-free decode path performs NO host
    syncs AT ALL between flushes: tokens park in the emission ring
    (DESIGN.md §18) and leave fused with the combined predicate in ONE
    3-item `token_emit` batch per window (+ the per-PACK prefill read),
    with NO disk reads — asserted via the hostsync and checkpoint counting
    hooks, Tier-0 snapshots (one launch a flush edge) included."""
    rc, params, _ = setup
    srv = SedarServer(rc, backend=backend)
    _serve(srv, params, validate_lag=8)            # warm the jit caches
    with hostsync.count_transfers() as st, count_disk_reads() as dr:
        out, rep = _serve(srv, params, validate_lag=8)
    assert not rep.detections
    allowed = {"token_emit", "prefill_emit", "deferred_flush"}
    assert set(st.by_label) <= allowed, st.by_label
    # emission is O(1/D): at most pred+toks+poss per flush window — NOT
    # the 2*steps items of the retired per-tick readback
    windows = rep.steps // 8 + 2
    assert st.by_label["token_emit"] <= 3 * windows, st.by_label
    assert st.by_label["token_emit"] < 2 * rep.steps
    # every token still reaches its stream through the drain path
    assert rep.tokens_emitted == sum(len(r.tokens) for r in out.values())
    # admission readback is ONE batch (tok+verdict) per PACK launch, not
    # per request — packing amortizes the host sync too (DESIGN.md §14)
    assert rep.prefill_packs > 0
    assert st.by_label["prefill_emit"] == 2 * rep.prefill_packs
    assert st.by_label["prefill_emit"] <= 2 * len(out)
    assert st.by_label.get("deferred_flush", 0) <= windows
    assert dr.reads == 0


@pytest.mark.parametrize("backend", ["sequential", "fused"])
def test_flush_snapshot_is_one_launch_of_primary_slot_images(
        setup, backend, monkeypatch):
    """At every clean flush edge ONE snapshot program (`slot_images`)
    writes each running slot's {cache, tok, pos} image into the ring,
    bitwise equal to that slot's slice of the primary replica at the edge;
    no other slot gets that version, and the `slot_snapshot` spans of the
    flush edges and of the packed admissions say `launches == 1`."""
    from repro import obs
    rc, params, _ = setup
    srv = SedarServer(rc, backend=backend)
    real_snapshot = SedarServer._snapshot_slots
    checked = []

    def spy(self, eng, dual, sched, ring, version, slot_arrays):
        # host copies now: the next decode step may donate `dual`
        prim = eng.executor.primary(dual)
        want = {k: jax.tree.map(np.asarray, prim[k])
                for k in ("cache", "tok", "pos")}
        launches = []
        real_images = eng.executor.slot_images
        eng.executor.slot_images = (
            lambda *a: launches.append(a) or real_images(*a))
        eng.executor.peek = None          # the edge peeks at nothing
        try:
            real_snapshot(self, eng, dual, sched, ring, version, slot_arrays)
        finally:
            del eng.executor.slot_images, eng.executor.peek
        assert len(launches) == 1
        running = {slot for slot, _req in sched.running_items()}
        for slot in range(SLOTS):
            if slot not in running:
                assert ring.newest_version(slot) != version
                continue
            got_v, got = ring.restore(slot, max_step=version)
            assert got_v == version
            ref = {k: jax.tree.map(lambda x, a=srv.slot_axes[k]: np.asarray(
                slot_image(x, slot, a)), v) for k, v in want.items()}
            assert jax.tree.structure(got) == jax.tree.structure(ref)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
                a = np.asarray(a)
                assert a.dtype == b.dtype and a.shape == b.shape
                assert a.tobytes() == b.tobytes(), (slot, version)
        checked.append(version)

    monkeypatch.setattr(SedarServer, "_snapshot_slots", spy)
    tr = obs.enable_trace()
    try:
        out, rep = srv.serve(params, _long_requests(), slots=SLOTS,
                             validate_lag=4)
    finally:
        obs.shutdown()
    assert not rep.detections and len(checked) >= 4
    assert all(r.status == "done" for r in out)
    flush = [e["args"] for e in tr.by_name("slot_snapshot")
             if e["args"]["at"] == "flush"]
    assert [a["step"] for a in flush] == checked
    assert all(a["launches"] == 1 for a in flush)
    assert {a["slots"] for a in flush} == {1, 2, SLOTS}
    # a packed admission cuts its snapshots with the same one program
    admit = [e["args"] for e in tr.by_name("slot_snapshot")
             if e["args"]["at"] == "admit"]
    assert admit and all(a["launches"] == 1 for a in admit)


def test_fused_rollback_restores_a_flush_edge_image(setup):
    """Lag 8, a slot fault after the first flush edge: the faulty slot
    rolls back to the one-launch snapshot cut at that edge (not to its
    admission image), and every stream equals the fault-free run."""
    rc, params, _ = setup
    clean, _ = SedarServer(rc, backend="fused").serve(
        params, _long_requests(), slots=SLOTS, validate_lag=8)
    srv = SedarServer(rc, backend="fused", inj_spec=_slot_spec(step=10))
    out, rep = srv.serve(params, _long_requests(), slots=SLOTS,
                         validate_lag=8)
    assert len(rep.detections) == 1 and rep.rollbacks == 1
    ev = rep.detections[0]
    assert ev.boundary == "deferred" and ev.detail["slots"] == [FAULT_SLOT]
    [(_eng, _ring, recovery)] = srv._batch_engines.values()
    # the tenants were all admitted at tick 0; the first flush edge is 8
    assert recovery.last_restore_info["slots"][FAULT_SLOT]["version"] == 8
    assert [r.tokens for r in out] == [r.tokens for r in clean]


def test_rollback_performs_zero_disk_reads(setup):
    """Per-request recovery is served ENTIRELY from the device ring: even
    the faulty path reads nothing from disk."""
    rc, params, _ = setup
    srv = SedarServer(rc, dual=True, inj_spec=_slot_spec())
    with count_disk_reads() as dr:
        _, rep = _serve(srv, params, validate_lag=4)
    assert rep.rollbacks == 1
    assert dr.reads == 0


# ---------------------------------------------------------------------------
# backend equality (sequential / fused / abft)
# ---------------------------------------------------------------------------

def test_fused_backend_equality_under_fault(setup):
    """Single-launch fused serving: same detection stream (step + slots)
    and bitwise-identical tokens as the sequential backend under the same
    injected decode fault."""
    rc, params, clean_toks = setup
    srv = SedarServer(rc, backend="fused", inj_spec=_slot_spec())
    out, rep = _serve(srv, params)
    assert len(rep.detections) == 1
    ev = rep.detections[0]
    assert (ev.step, ev.boundary, ev.detail["slots"]) == \
        (FAULT_STEP, "commit", [FAULT_SLOT])
    _assert_streams_equal(out, clean_toks)


@pytest.mark.parametrize("lag", [4, 8])
def test_fused_backend_deferred_equality(setup, lag):
    """Fused deferred serving at lag 4 and 8: the flush localizes the
    faulty slot, it rolls back from the Tier-0 ring, and every stream
    still equals the fault-free run."""
    rc, params, clean_toks = setup
    srv = SedarServer(rc, backend="fused", inj_spec=_slot_spec())
    out, rep = _serve(srv, params, validate_lag=lag)
    assert rep.detections[0].boundary == "deferred"
    assert rep.detections[0].detail["slots"] == [FAULT_SLOT]
    assert rep.rollbacks == 1
    _assert_streams_equal(out, clean_toks)


def test_abft_serve_forward_corrects_and_emits(setup):
    """Replica-free serving: a kernel-domain fault inside the checksummed
    logits block is forward-corrected in place — the corrected commit EMITS
    its token (rollbacks=0, no re-execution) and the streams equal the
    dual-replica clean run."""
    rc, params, clean_toks = setup
    V = rc.model.vocab_size
    spec = InjectionSpec(leaf_idx=0, flat_idx=FAULT_SLOT * (V + 1) + 5,
                         bit=30, step=FAULT_STEP, replica=0, target="kernel")
    srv = SedarServer(rc, backend="abft", inj_spec=spec)
    out, rep = _serve(srv, params)
    assert len(rep.detections) == 1
    assert rep.detections[0].detail.get("abft_corrected")
    assert rep.retries == 0 and rep.rollbacks == 0
    eng = srv._batch_engines[next(iter(srv._batch_engines))][0]
    assert [r["kind"] for r in eng.recoveries] == ["abft_correct"]
    assert all(r.status == "done" for r in out.values())
    _assert_streams_equal(out, clean_toks)


def test_abft_generate_forward_correct_emits_token():
    """The generate() NB path: an ABFT-corrected commit advances the decode
    state and its token is emitted instead of re-executing the step."""
    rc = _cfg()
    srv_c = SedarServer(rc)
    params = srv_c.model.init(jax.random.PRNGKey(0))
    prompt = {"tokens": jnp.asarray(
        np.random.RandomState(0).randint(0, 200, (2, 8)), jnp.int32)}
    clean, _ = srv_c.generate(params, prompt, steps=6)
    B, V = 2, rc.model.vocab_size
    spec = InjectionSpec(leaf_idx=0, flat_idx=1 * (V + 1) + 5, bit=30,
                         step=10, replica=0, target="kernel")
    srv = SedarServer(rc, backend="abft", inj_spec=spec)
    toks, rep = srv.generate(params, prompt, steps=6)
    assert len(rep.detections) == 1
    assert rep.detections[0].detail.get("abft_corrected")
    assert rep.retries == 0 and not rep.stopped
    assert [r["kind"] for r in srv.engine.recoveries] == ["abft_correct"]
    np.testing.assert_array_equal(toks, clean)
