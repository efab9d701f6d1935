"""End-to-end observability (DESIGN.md §15): a fault campaign across
backends must leave a journal that reconstructs the engine's exact
event/recovery sequence byte-for-byte, KPIs that honor the temporal-model
bounds (MTTD <= validate_lag), and — the hard contract — metrics+journal
enabled must add ZERO host syncs to the fault-free protected step."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import RunConfig, SedarConfig, TrainConfig, get_config, \
    reduce_for_smoke
from repro.core import hostsync
from repro.core.detection import SedarSafeStop
from repro.core.fingerprint import pytree_fingerprint, \
    pytree_fingerprint_fused
from repro.core.injection import InjectionSpec, MemoryInjectionFlag, \
    inject_tree
from repro.core.policy import make_engine
from repro.runtime.serve import SedarServer


@pytest.fixture(autouse=True)
def _obs_teardown():
    yield
    obs.shutdown()


# -- toy protected-train harness (same shape as test_deferred's) --------------

def _toy_step_fn(spec):
    def step_fn(state, batch, replica_id, armed):
        delta = 0.1 * batch - 0.01 * state["x"]
        if spec is not None:
            delta = inject_tree({"d": delta}, spec, step=state["step"],
                                replica_id=replica_id, armed=armed)["d"]
        fp = pytree_fingerprint_fused({"d": delta})
        cand = {"x": state["x"] + delta, "step": state["step"] + 1}
        return cand, fp, jnp.sum(cand["x"])

    return jax.jit(step_fn)


def _toy_engine(workdir, level, spec=None, backend="fused", lag=1,
                ckpt_interval=3):
    sedar = SedarConfig(level=level, replication=backend,
                        validate_interval=1, validate_lag=lag,
                        param_validate_interval=0,
                        checkpoint_interval=ckpt_interval,
                        checkpoint_dir=os.path.join(workdir, "ckpt"))
    state_fp = jax.jit(lambda s: pytree_fingerprint({"x": s["x"]}))
    fast_fp = jax.jit(lambda s: pytree_fingerprint_fused({"x": s["x"]}))

    def init_single():
        return {"x": jnp.zeros((16,), jnp.float32),
                "step": jnp.zeros((), jnp.int32)}

    eng = make_engine(sedar, backend=backend, workdir=workdir,
                      step_fn=_toy_step_fn(spec), state_fp_fn=state_fp,
                      fast_state_fp_fn=fast_fp, inj_spec=spec,
                      inj_flag=MemoryInjectionFlag(),
                      init_fn=lambda: eng.executor.init_dual(init_single()),
                      notify=lambda e: None)
    return eng


def _drive(eng, num_steps, max_iters=100):
    dual = eng.init_dual()
    eng.reset()
    step = int(np.asarray(eng.executor.peek(dual, "step")))
    stopped, it = False, 0
    while True:
        if step >= num_steps:
            event = eng.flush_deferred()
            if event is None:
                break
            try:
                dual = eng.on_detection(event, dual)
            except SedarSafeStop:
                stopped = True
                break
            step = int(np.asarray(eng.executor.peek(dual, "step")))
            continue
        it += 1
        assert it < max_iters, "engine did not converge"
        batch = jnp.full((16,), float(step + 1), jnp.float32)
        outcome = eng.run_protected_step(dual, batch, step)
        dual = outcome.dual
        if outcome.committed and outcome.aux is not None:
            step += 1
        if outcome.event is not None:
            try:
                dual = eng.on_detection(outcome.event, dual)
            except SedarSafeStop:
                stopped = True
                break
            step = int(np.asarray(eng.executor.peek(dual, "step")))
    store = getattr(eng.recovery, "store", None)
    if store is not None:
        store.wait()
    return dual, stopped


SPEC = InjectionSpec(leaf_idx=0, flat_idx=5, bit=20, step=4, replica=1,
                     target="grads")
LAG = 8


# -- serve harness (same shape as test_serve_batched's) -----------------------

SLOTS = 3
FAULT_SLOT = 1
FAULT_STEP = 3


def _serve_cfg():
    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    return RunConfig(model=cfg, train=TrainConfig(global_batch=2, seq_len=8))


def _serve_requests():
    from repro.runtime.scheduler import synthetic_requests
    return synthetic_requests(5, arrival_rate=2.0, prompt_lengths=(4, 8),
                              max_new_choices=(4, 8), seed=1)


# ---------------------------------------------------------------------------
# journal == engine records, byte for byte (train campaign, transient fault)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["sequential", "fused"])
def test_train_campaign_journal_reconstructs_engine(tmp_workdir, backend):
    """Deferred transient fault: the journal's detection/recovery payloads
    must reproduce eng.detections / eng.recoveries byte-for-byte (including
    the restore-planner fields merged in AFTER the recovery record was
    appended), and MTTD must respect the validate_lag bound."""
    obs.enable_metrics()
    j = obs.FaultJournal()
    obs.set_journal(j)
    eng = _toy_engine(tmp_workdir, 2, spec=SPEC, backend=backend, lag=LAG)
    _, stopped = _drive(eng, 12)
    assert not stopped
    assert len(eng.detections) == 1 and eng.recoveries

    verdict = obs.reconcile(j.records(), eng.detections, eng.recoveries)
    assert verdict == {"detections_match": True, "recoveries_match": True}
    # the journaled recovery carries the tier info merged post-append
    jrec = obs.payloads(j.records(), "recovery", "record")
    assert jrec[0]["kind"] == "restore"
    assert obs.canonical(jrec[0]) == obs.canonical(eng.recoveries[0])

    kpis = obs.compute_kpis(j.records(), steps=12, injected=1)
    assert 0 < kpis["mttd_max_steps"] <= LAG
    assert kpis["sdc_coverage"] == 1.0
    rows = obs.reconcile_with_advice(kpis, validate_lag=LAG)
    assert all(r["ok"] for r in rows), rows
    # the metric stream agrees with the engine lists
    assert obs.metrics.get("sedar_detections_total", boundary="deferred",
                           effect="TDC") == 1
    assert obs.metrics.get("sedar_recoveries_total", kind="restore") == \
        sum(1 for r in eng.recoveries if r["kind"] == "restore")


def test_train_l1_stop_is_journaled(tmp_workdir):
    """The safe-stop recovery record reaches the journal even though
    on_detection raises (the finally-path journaling)."""
    j = obs.FaultJournal()
    obs.set_journal(j)
    eng = _toy_engine(tmp_workdir, 1, spec=SPEC, backend="fused", lag=4,
                      ckpt_interval=0)
    _, stopped = _drive(eng, 10)
    assert stopped
    verdict = obs.reconcile(j.records(), eng.detections, eng.recoveries)
    assert verdict == {"detections_match": True, "recoveries_match": True}
    assert obs.payloads(j.records(), "recovery", "record")[0]["kind"] == \
        "stop"


# ---------------------------------------------------------------------------
# serve campaigns: corrected (abft/hybrid) + persistent (rejection)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["abft", "hybrid"])
def test_serve_corrected_fault_journal(backend):
    """Replica-free serving with a kernel-domain fault: the forward
    correction's detection + recovery records land in the journal exactly
    as the engine reports them."""
    rc = _serve_cfg()
    V = rc.model.vocab_size
    spec = InjectionSpec(leaf_idx=0, flat_idx=FAULT_SLOT * (V + 1) + 5,
                         bit=30, step=FAULT_STEP, replica=0, target="kernel")
    obs.enable_metrics()
    j = obs.FaultJournal()
    obs.set_journal(j)
    srv = SedarServer(rc, backend=backend, inj_spec=spec)
    params = srv.model.init(jax.random.PRNGKey(0))
    out, rep = srv.serve(params, _serve_requests(), slots=SLOTS)
    assert len(rep.detections) == 1
    assert rep.detections[0].detail.get("abft_corrected")
    eng = srv._batch_engines[next(iter(srv._batch_engines))][0]
    verdict = obs.reconcile(j.records(), eng.detections, eng.recoveries)
    assert verdict == {"detections_match": True, "recoveries_match": True}
    kpis = obs.compute_kpis(j.records(), steps=rep.steps,
                            tokens=rep.tokens_emitted, injected=1)
    assert kpis["corrected"] >= 1
    assert kpis["sdc_coverage"] == 1.0
    assert obs.metrics.get("serve_tokens_emitted_total") > 0


def test_serve_persistent_fault_rejection_journaled():
    """A stuck bit exhausts the per-request budget: the journal's rejection
    line names the same request the server rejected, and the rejection
    counter matches."""
    rc = _serve_cfg()
    spec = InjectionSpec(leaf_idx=FAULT_SLOT, flat_idx=7, bit=30,
                         step=FAULT_STEP, replica=1, target="slot",
                         persistent=True)
    obs.enable_metrics()
    j = obs.FaultJournal()
    obs.set_journal(j)
    srv = SedarServer(rc, dual=True, max_retries=3, inj_spec=spec)
    params = srv.model.init(jax.random.PRNGKey(0))
    out, rep = srv.serve(params, _serve_requests(), slots=SLOTS)
    assert rep.rejected and not rep.stopped
    rej = j.records("rejection")
    assert [r["rid"] for r in rej] == rep.rejected
    assert all(r["reason"] == "persistent_fault" for r in rej)
    assert obs.metrics.get("serve_rejections_total",
                           reason="persistent_fault") == len(rep.rejected)
    # the detection stream that led there is journaled too
    assert len(j.records("detection")) == len(rep.detections)


def test_serve_backpressure_rejections_journaled():
    from repro.runtime.scheduler import synthetic_requests
    rc = _serve_cfg()
    j = obs.FaultJournal()
    obs.set_journal(j)
    srv = SedarServer(rc, dual=True)
    params = srv.model.init(jax.random.PRNGKey(0))
    reqs = synthetic_requests(6, arrival_rate=100.0, seed=2)
    out, rep = srv.serve(params, reqs, slots=2, queue_depth=2)
    shed = j.records("rejection")
    assert [r["rid"] for r in shed] == rep.rejected
    assert all(r["reason"] == "backpressure" for r in shed)


# ---------------------------------------------------------------------------
# the zero-extra-hostsync contract (acceptance criterion)
# ---------------------------------------------------------------------------

def test_metrics_on_adds_zero_host_syncs(tmp_workdir):
    """Fault-free protected steps at lag>=8: the count_transfers label map
    with metrics + journal + trace enabled must EQUAL the metrics-off map —
    telemetry only piggybacks on readbacks the engine already performs."""

    def run(workdir):
        eng = _toy_engine(workdir, 2, backend="fused", lag=LAG,
                          ckpt_interval=100)
        dual = eng.init_dual()
        eng.reset()
        eng.run_protected_step(dual, jnp.ones((16,), jnp.float32), 0)  # jit
        dual = eng.init_dual()
        eng.reset()
        with hostsync.count_transfers() as st:
            for s in range(LAG):
                out = eng.run_protected_step(
                    dual, jnp.full((16,), float(s + 1), jnp.float32), s)
                dual = out.dual
                assert out.event is None
        return st

    off = run(tmp_workdir + "_off")
    assert not obs.metrics_enabled()

    obs.enable_metrics()
    obs.set_journal(obs.FaultJournal())
    obs.enable_trace()
    on = run(tmp_workdir + "_on")

    assert on.by_label == off.by_label
    assert on.transfers == off.transfers == 1    # the single window flush
    assert on.by_label == {"deferred_flush": 1}
    # and the registry saw exactly that one readback — through the shim
    # hook, not through any readback of its own
    assert obs.metrics.get("hostsync_transfers_total",
                           label="deferred_flush") == 1


def _serve_transfers(rc, params):
    """Per-label transfer counts of one fault-free serve at lag=8, taken
    after a warm-up call."""
    srv = SedarServer(rc, dual=True)
    srv.serve(params, _serve_requests(), slots=SLOTS,
              validate_lag=8)                          # warm the jit cache
    with hostsync.count_transfers() as st:
        _, rep = srv.serve(params, _serve_requests(), slots=SLOTS,
                           validate_lag=8)
    assert not rep.detections
    return st


def test_metrics_on_serve_same_transfer_labels():
    """The same contract through the full continuous-batching loop: the
    per-label transfer counts of a fault-free serve at lag=8 are identical
    with metrics+journal on vs off."""
    rc = _serve_cfg()
    params = SedarServer(rc, dual=True).model.init(jax.random.PRNGKey(0))
    off = _serve_transfers(rc, params)
    obs.enable_metrics()
    obs.set_journal(obs.FaultJournal())
    on = _serve_transfers(rc, params)
    assert on.by_label == off.by_label, (on.by_label, off.by_label)


def test_trace_on_serve_same_transfer_labels():
    """Trace spans on top of metrics+journal: the serve loop's per-label
    transfer counts still equal the telemetry-off run's."""
    rc = _serve_cfg()
    params = SedarServer(rc, dual=True).model.init(jax.random.PRNGKey(0))
    off = _serve_transfers(rc, params)
    obs.enable_metrics()
    obs.set_journal(obs.FaultJournal())
    obs.enable_trace()
    on = _serve_transfers(rc, params)
    assert on.by_label == off.by_label, (on.by_label, off.by_label)
