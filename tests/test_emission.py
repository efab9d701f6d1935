"""Lag-aligned token emission (DESIGN.md §18): TokenRing unit semantics,
rollback retraction, the detokenize consumer, and the serving-level oracle
— delivered streams bitwise identical to lag=1 under injected faults, with
un-drained tokens retracted by construction (never delivered then undone).
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, TrainConfig, get_config, \
    reduce_for_smoke
from repro.core import hostsync
from repro.core.injection import InjectionSpec
from repro.runtime.emission import DetokenizeConsumer, DrainBatch, \
    TokenRing, deliver_batch
from repro.runtime.scheduler import Request, synthetic_requests
from repro.runtime.serve import SedarServer

SLOTS = 3
FAULT_SLOT = 1


def _req(rid=0, pos0=4, prefill_tok=11):
    r = Request(rid=rid, prompt=np.arange(4, dtype=np.int32),
                max_new_tokens=8)
    r.pos0 = pos0
    r.tokens = [prefill_tok]
    r.token_times = [0.0]
    r.truncated_tokens = 0
    return r


def _park_window(ring, req, toks, start_pos):
    """Park len(toks) single-slot ticks with consecutive positions."""
    ring.owners = {0: req}
    for i, tk in enumerate(toks):
        ring.park(i, (jnp.asarray([[tk]], jnp.int32),
                      jnp.asarray([start_pos + i], jnp.int32)))


# ---------------------------------------------------------------------------
# TokenRing unit semantics
# ---------------------------------------------------------------------------

def test_ring_cadence_gates_provide():
    ring = TokenRing(cadence=3)
    req = _req()
    _park_window(ring, req, [21, 22], start_pos=5)
    assert len(ring) == 2 and ring.parked == 2
    assert ring.provide() is None            # 2 < cadence
    leaves = ring.provide(final=True)        # final forces the drain
    assert leaves is not None and len(leaves) == 2
    assert leaves[0].shape == (2, 1, 1) and leaves[1].shape == (2, 1)
    ring.park(2, (jnp.asarray([[23]], jnp.int32),
                  jnp.asarray([7], jnp.int32)))
    assert ring.provide() is not None        # cadence met

    vals = hostsync.batched_get(ring.provide(), label="test")
    batch = ring.deliver(vals)
    assert len(ring) == 0 and ring.drains == 1
    assert batch.steps == [0, 1, 2]
    assert req.tokens == [11, 21, 22, 23]    # inline sink delivered in order
    assert ring.delivered == 3 and ring.retracted == 0


def test_ring_owner_snapshot_survives_slot_reuse():
    """park() copies the owner map, so re-admitting a new request into the
    slot mid-window cannot reroute already-parked rows."""
    ring = TokenRing(cadence=4)
    old, new = _req(rid=0), _req(rid=1, pos0=10, prefill_tok=50)
    _park_window(ring, old, [21, 22], start_pos=5)
    ring.owners = {0: new}                   # slot re-admitted
    ring.park(2, (jnp.asarray([[61]], jnp.int32),
                  jnp.asarray([11], jnp.int32)))
    ring.park(3, (jnp.asarray([[62]], jnp.int32),
                  jnp.asarray([12], jnp.int32)))
    vals = [np.asarray(x) for x in jax.device_get(ring.provide())]
    ring.deliver(vals)
    assert old.tokens == [11, 21, 22]
    assert new.tokens == [50, 61, 62]


def test_truncate_retracts_at_or_after_first_bad():
    """slot_first_bad dead-marks the faulty slot's rows from its first bad
    step on: earlier rows deliver, later rows count as truncated."""
    ring = TokenRing(cadence=4)
    req = _req()
    _park_window(ring, req, [21, 22, 23, 24], start_pos=5)
    ring.truncate({0: 1})                    # steps 1..3 are bad for slot 0
    vals = [np.asarray(x) for x in jax.device_get(ring.provide())]
    ring.deliver(vals)
    assert req.tokens == [11, 21]            # step-0 row was clean
    assert req.truncated_tokens == 3
    assert ring.delivered == 1 and ring.retracted == 3


def test_truncate_global_bad_and_frozen_dedup():
    """Scalar-predicate fallback (no slot localization) dead-marks whole
    rows; a frozen slot's REPEATED position is retracted once, not per
    occurrence (the virtual-length walk)."""
    ring = TokenRing(cadence=4)
    req = _req()
    ring.owners = {0: req}
    for step, pos in [(0, 5), (1, 6), (2, 6), (3, 6)]:   # frozen at pos 6
        ring.park(step, (jnp.asarray([[30 + step]], jnp.int32),
                         jnp.asarray([pos], jnp.int32)))
    ring.truncate(None, global_bad=1)
    vals = [np.asarray(x) for x in jax.device_get(ring.provide())]
    ring.deliver(vals)
    assert req.tokens == [11, 30]
    assert req.truncated_tokens == 1         # pos 6 counted once


def test_deliver_batch_prefix_guard_is_exactly_once():
    """Delivered-prefix property: a token lands only when its position
    extends the stream by exactly one — duplicate drains and regressed
    positions are no-ops."""
    req = _req()
    toks = np.asarray([[[21]], [[21]], [[22]]], np.int32)   # dup row
    poss = np.asarray([[5], [5], [6]], np.int32)
    batch = DrainBatch(steps=[0, 1, 2], toks=toks, poss=poss,
                       owners=[{0: req}] * 3, dead=[set(), set(), set()],
                       dead_all=[False] * 3)
    d, r = deliver_batch(batch, now=1.0)
    assert (d, r) == (2, 0)
    assert req.tokens == [11, 21, 22]
    assert req.token_times[1:] == [1.0, 1.0]
    # replaying the whole batch delivers nothing new
    assert deliver_batch(batch, now=2.0) == (0, 0)
    assert req.tokens == [11, 21, 22]


def test_on_token_streams_in_order():
    seen = []
    req = _req()
    ring = TokenRing(cadence=2,
                     on_token=lambda r, tok, i: seen.append((r.rid, i, tok)))
    _park_window(ring, req, [21, 22], start_pos=5)
    vals = [np.asarray(x) for x in jax.device_get(ring.provide())]
    ring.deliver(vals)
    assert seen == [(0, 1, 21), (0, 2, 22)]


# ---------------------------------------------------------------------------
# detokenize consumer
# ---------------------------------------------------------------------------

def _batch_for(req, toks, start_pos):
    n = len(toks)
    return DrainBatch(
        steps=list(range(n)),
        toks=np.asarray(toks, np.int32).reshape(n, 1, 1),
        poss=np.asarray([start_pos + i for i in range(n)],
                        np.int32).reshape(n, 1),
        owners=[{0: req}] * n, dead=[set() for _ in range(n)],
        dead_all=[False] * n)


def test_consumer_threaded_delivery_and_quiesce():
    req = _req()
    cons = DetokenizeConsumer(max_queue=4).start()
    cons.submit(_batch_for(req, [21, 22], 5))
    cons.submit(_batch_for(req, [23], 7))
    cons.quiesce()                           # blocks until both are walked
    assert req.tokens == [11, 21, 22, 23]
    assert cons.batches == 2 and cons.delivered == 3
    cons.close()


def test_consumer_inline_fallback_without_start():
    req = _req()
    cons = DetokenizeConsumer()
    cons.submit(_batch_for(req, [21], 5))    # no thread: delivered inline
    assert req.tokens == [11, 21] and cons.batches == 1
    cons.close()                             # no-op, no thread to join


def test_consumer_close_surfaces_worker_error():
    cons = DetokenizeConsumer(max_queue=2).start()
    bad = DrainBatch(steps=[0], toks=np.zeros((1, 1, 1), np.int32),
                     poss=np.zeros((1, 1), np.int32),
                     owners=[{0: object()}],   # no .pos0 -> worker raises
                     dead=[set()], dead_all=[False])
    cons.submit(bad)
    with pytest.raises(AttributeError):
        cons.close()
    assert cons.errors


def test_consumer_backpressure_blocks_submit():
    """A full queue makes submit() wait for the worker — memory stays
    bounded behind a slow client instead of batches piling up."""
    gate = threading.Event()
    req = _req()
    cons = DetokenizeConsumer(
        on_token=lambda *a: gate.wait(timeout=5.0), max_queue=1).start()
    cons.submit(_batch_for(req, [21], 5))    # worker blocks inside on_token
    time.sleep(0.02)
    cons.submit(_batch_for(req, [22], 6))    # fills the queue
    t0 = time.monotonic()
    release = threading.Timer(0.15, gate.set)
    release.start()
    cons.submit(_batch_for(req, [23], 7))    # must WAIT for the worker
    assert time.monotonic() - t0 > 0.05
    cons.quiesce()
    cons.close()
    release.join()
    assert req.tokens == [11, 21, 22, 23]
    assert cons.backlog_peak >= 1


# ---------------------------------------------------------------------------
# serving-level oracle: streams bitwise identical to lag=1 under faults
# ---------------------------------------------------------------------------

def _cfg():
    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    return RunConfig(model=cfg, train=TrainConfig(global_batch=2, seq_len=8))


def _requests():
    return synthetic_requests(5, arrival_rate=2.0, prompt_lengths=(4, 8),
                              max_new_choices=(4, 8), seed=1)


def _slot_spec(step, **kw):
    kw.setdefault("target", "slot")
    return InjectionSpec(leaf_idx=FAULT_SLOT, flat_idx=7, bit=30,
                         step=step, replica=1, **kw)


@pytest.fixture(scope="module")
def oracle():
    """Fault-free lag=1 streams: the bitwise ground truth every drain-mode
    campaign must reproduce."""
    rc = _cfg()
    srv = SedarServer(rc, dual=True)
    params = srv.model.init(jax.random.PRNGKey(0))
    reqs, rep = srv.serve(params, _requests(), slots=SLOTS, validate_lag=1)
    assert not rep.detections
    return rc, params, {r.rid: list(r.tokens) for r in reqs}


def _assert_streams_equal(out, clean_toks):
    for r in out:
        assert list(r.tokens) == clean_toks[r.rid], f"request {r.rid}"


@pytest.mark.parametrize("lag,fault_step", [(4, 5), (8, 3)])
def test_midwindow_fault_retracts_and_matches_lag1(oracle, lag, fault_step):
    """A slot SDC strictly inside the deferred window: the failed flush
    dead-marks the slot's un-drained rows (retraction by construction —
    they were never delivered), the slot rolls back and re-decodes, and
    EVERY stream — affected and unaffected — is bitwise identical to the
    lag=1 run."""
    rc, params, clean_toks = oracle
    srv = SedarServer(rc, dual=True, inj_spec=_slot_spec(fault_step))
    out, rep = srv.serve(params, _requests(), slots=SLOTS, validate_lag=lag)
    assert len(rep.detections) == 1
    ev = rep.detections[0]
    assert ev.boundary == "deferred" and ev.step == fault_step
    assert ev.detail["slots"] == [FAULT_SLOT]
    assert rep.rollbacks == 1
    assert rep.truncated_tokens > 0          # un-drained rows were retracted
    assert all(r.status == "done" for r in out)
    _assert_streams_equal(out, clean_toks)
    assert sum(1 for r in out if r.truncated_tokens > 0) == 1


@pytest.mark.parametrize("lag", [4, 8])
def test_persistent_stuck_bit_rejects_under_drain(oracle, lag):
    """A stuck bit re-injected every step: the per-request budget exhausts,
    THAT request is rejected after the consumer quiesces (the notify
    callback sees a settled stream), and everyone else's delivered stream
    still equals lag=1."""
    rc, params, clean_toks = oracle
    notified = []
    srv = SedarServer(rc, dual=True, max_retries=3,
                      inj_spec=_slot_spec(3, persistent=True))
    out, rep = srv.serve(params, _requests(), slots=SLOTS, validate_lag=lag,
                         notify_reject=lambda r, e: notified.append(r.rid))
    rejected = [r for r in out if r.status == "rejected"]
    assert len(rejected) == 1
    assert rep.rejected == [rejected[0].rid] == notified
    assert not rep.stopped
    for r in out:
        if r.status == "done":
            assert list(r.tokens) == clean_toks[r.rid]


def test_fused_backend_drain_equality(oracle):
    rc, params, clean_toks = oracle
    srv = SedarServer(rc, backend="fused", inj_spec=_slot_spec(3))
    out, rep = srv.serve(params, _requests(), slots=SLOTS, validate_lag=4)
    assert rep.detections and rep.detections[0].boundary == "deferred"
    assert rep.detections[0].detail["slots"] == [FAULT_SLOT]
    assert rep.rollbacks == 1
    _assert_streams_equal(out, clean_toks)


def test_abft_backend_drain_equality(oracle):
    """Replica-free backend under drain: a kernel-domain fault inside the
    checksummed logits block is forward-corrected in place, so the window
    flushes clean and the drained streams equal the dual-replica lag=1
    oracle with zero rollbacks."""
    rc, params, clean_toks = oracle
    V = rc.model.vocab_size
    spec = InjectionSpec(leaf_idx=0, flat_idx=FAULT_SLOT * (V + 1) + 5,
                         bit=30, step=3, replica=0, target="kernel")
    srv = SedarServer(rc, backend="abft", inj_spec=spec)
    out, rep = srv.serve(params, _requests(), slots=SLOTS, validate_lag=4)
    assert rep.rollbacks == 0
    assert all(r.status == "done" for r in out)
    _assert_streams_equal(out, clean_toks)


def test_delivered_prefix_property_under_fault(oracle):
    """on_token observes the stream AS DELIVERED (from the consumer
    thread): per request, indices are gapless and strictly increasing, and
    the observed sequence IS the final stream — nothing was ever delivered
    and later taken back, even though a mid-window fault forced retraction
    of parked rows."""
    rc, params, clean_toks = oracle
    streamed, first_idx = {}, {}

    def on_token(req, tok, idx):
        seq = streamed.setdefault(req.rid, [])
        if not seq:
            first_idx[req.rid] = idx
        assert idx == first_idx[req.rid] + len(seq), \
            "delivery skipped or repeated a position"
        seq.append(tok)

    srv = SedarServer(rc, dual=True, inj_spec=_slot_spec(3))
    out, rep = srv.serve(params, _requests(), slots=SLOTS, validate_lag=8,
                         on_token=on_token)
    assert rep.rollbacks == 1
    _assert_streams_equal(out, clean_toks)
    for r in out:
        # index 0 is the prefill token, delivered at admission (not
        # streamed); everything after it streamed gaplessly in order
        seq = streamed.get(r.rid, [])
        if seq:
            assert first_idx[r.rid] == 1
        assert seq == list(r.tokens)[1:]


def test_run_ending_midwindow_releases_exactly_once(oracle):
    """Regression (satellite 6): a drainer whose finishing window is
    drained by the FINAL partial flush must release exactly once — every
    completed rid appears once in rep.completed, no slot is stranded
    DRAINING, and the delivered tokens survive the early exit."""
    rc, params, clean_toks = oracle
    srv = SedarServer(rc, dual=True)
    # cap mid-window: lag=8 but only ~6 decode ticks fit
    out, rep = srv.serve(params, _requests(), slots=SLOTS, validate_lag=8,
                         max_steps=6)
    assert sorted(rep.completed) == sorted(set(rep.completed))
    assert all(r.status != "draining" for r in out)
    done = [r for r in out if r.status == "done"]
    assert {r.rid for r in done} == set(rep.completed)
    for r in done:
        assert list(r.tokens) == clean_toks[r.rid]
    # partial streams are PREFIXES of the oracle (delivered-prefix holds
    # even for requests the cap cut off)
    for r in out:
        assert list(r.tokens) == clean_toks[r.rid][:len(r.tokens)]
