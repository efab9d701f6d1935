"""Closed-loop observability (DESIGN.md §17): online calibration, drift
detection, alerting, and live reconfiguration of the protection knobs.

Covers the full estimate -> detect -> re-advise -> apply loop at three
granularities: pure-python units (estimator, detectors, alert manager),
the Autotuner's hysteresis/burst policy against a stub engine, and the
real toy engine end-to-end — including the acceptance criteria that every
alert/reconfig reconstructs byte-for-byte from the journal and that a
fault-free protected run has IDENTICAL host-sync label maps with the
autotuner on vs off."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import SedarConfig
from repro.core import hostsync
from repro.core import temporal_model as tm
from repro.core.fingerprint import pytree_fingerprint, \
    pytree_fingerprint_fused
from repro.core.injection import MemoryInjectionFlag
from repro.core.policy import Autotuner, AutotuneConfig, autotune, \
    make_engine
from repro.obs.alerts import Alert, AlertManager, SloTracker
from repro.obs.anomaly import AnomalyMonitor, Cusum, EwmaBand, PageHinkley
from repro.obs.estimator import OnlineEstimator
from repro.obs.journal import FaultJournal
from repro.obs.registry import MetricsRegistry

BASE = tm.PAPER_TABLE3["JACOBI"]


@pytest.fixture(autouse=True)
def _obs_teardown():
    yield
    obs.shutdown()


# -- toy protected-train harness (same shape as test_observability_e2e) ------

def _toy_step_fn():
    def step_fn(state, batch, replica_id, armed):
        delta = 0.1 * batch - 0.01 * state["x"]
        fp = pytree_fingerprint_fused({"d": delta})
        cand = {"x": state["x"] + delta, "step": state["step"] + 1}
        return cand, fp, jnp.sum(cand["x"])

    return jax.jit(step_fn)


def _toy_engine(workdir, lag=4, ckpt_interval=3):
    sedar = SedarConfig(level=2, replication="fused",
                        validate_interval=1, validate_lag=lag,
                        param_validate_interval=0,
                        checkpoint_interval=ckpt_interval,
                        checkpoint_dir=os.path.join(workdir, "ckpt"))
    state_fp = jax.jit(lambda s: pytree_fingerprint({"x": s["x"]}))
    fast_fp = jax.jit(lambda s: pytree_fingerprint_fused({"x": s["x"]}))

    def init_single():
        return {"x": jnp.zeros((16,), jnp.float32),
                "step": jnp.zeros((), jnp.int32)}

    eng = make_engine(sedar, backend="fused", workdir=workdir,
                      step_fn=_toy_step_fn(), state_fp_fn=state_fp,
                      fast_state_fp_fn=fast_fp,
                      inj_flag=MemoryInjectionFlag(),
                      init_fn=lambda: eng.executor.init_dual(init_single()),
                      notify=lambda e: None)
    return eng


class _StubEngine:
    """Just enough engine surface for Autotuner hysteresis tests: a lag,
    a pending flag, and an apply_reconfig that records transitions."""

    def __init__(self, lag=8):
        self.validate_lag = lag
        self.pending_validation = False
        self.recovery = type("R", (), {"tiers": None})()
        self.reconfigs = []

    def apply_reconfig(self, *, validate_lag=None, checkpoint_interval=None,
                       tier_schedule=None, reason=""):
        if self.pending_validation:
            return None
        if validate_lag is None or int(validate_lag) == self.validate_lag:
            return None
        rec = {"kind": "reconfig", "step": 0, "reason": str(reason),
               "changes": {"validate_lag": {"from": self.validate_lag,
                                            "to": int(validate_lag)}}}
        self.validate_lag = int(validate_lag)
        self.reconfigs.append(rec)
        return rec


def _calibrate_storm(est, n_steps=64, gap_s=72.0, n_faults=12):
    """Feed a fully-confident storm calibration: 2s steps, 4s syncs, and
    faults every ``gap_s`` (72s = 0.02h MTBE — the storm of PHASES below)."""
    for _ in range(n_steps):
        est.observe_step_s(2.0)
    for _ in range(8):
        est.observe_sync_s(4.0)
    t = 0.0
    for _ in range(n_faults):
        est.observe_fault(t)
        t += gap_s


# ---------------------------------------------------------------------------
# estimator
# ---------------------------------------------------------------------------

def test_estimator_calibrates_step_and_sync():
    est = OnlineEstimator(BASE)
    _calibrate_storm(est, n_faults=0)
    snap = est.calibrated_params()
    assert snap.params.t_step * 3600.0 == pytest.approx(2.0)
    assert snap.params.t_sync * 3600.0 == pytest.approx(4.0)
    assert snap.confidence == 1.0
    assert snap.sample_counts["step"] == 64
    # untouched params come from the base table
    assert snap.params.T_rest == BASE.T_rest


def test_estimator_mtbe_prior_then_measured():
    est = OnlineEstimator(BASE, prior_mtbe_hours=24.0)
    # nothing observed: the prior pseudo-observation dominates
    assert est.mtbe_hours() == pytest.approx(24.0)
    # one detection, 1h of progress: still prior-anchored, now split
    est.observe_step_s(3600.0)
    est.observe_fault(3600.0)
    assert est.mtbe_hours() == pytest.approx((1.0 + 24.0) / 2.0)
    # >= 2 gaps: the measured gap EWMA takes over entirely
    est.observe_fault(2 * 3600.0)
    est.observe_fault(3 * 3600.0)
    assert est.mtbe_hours() == pytest.approx(1.0)


def test_estimator_tracks_mtbe_shift():
    """Calm (1h gaps) then storm (72s gaps): the EWMA must converge to the
    storm rate — the quantity the lag retarget keys on."""
    est = OnlineEstimator(BASE)
    t = 0.0
    for _ in range(6):
        t += 3600.0
        est.observe_fault(t)
    assert est.mtbe_hours() == pytest.approx(1.0)
    for _ in range(30):
        t += 72.0
        est.observe_fault(t)
    assert abs(est.mtbe_hours() - 0.02) / 0.02 < 0.2
    snap = est.calibrated_params()
    assert snap.sample_counts["detections"] == 36


def test_estimator_ingest_is_delta_based():
    """Repeated ingest of the same registry/journal must not double-count;
    new samples since the cursor fold in at the per-stage mean."""
    m = MetricsRegistry()
    for _ in range(10):
        m.observe("sedar_stage_duration_seconds", 2.0, stage="train_step")
    m.observe("sedar_stage_duration_seconds", 4.0, stage="deferred_flush")
    j = FaultJournal()
    j.append("detection", step=3,
             event={"step": 3, "boundary": "deferred", "effect": "TDC",
                    "detail": {}})
    j.append("detection", step=5,
             event={"step": 5, "boundary": "commit", "effect": "hang",
                    "detail": {}})

    est = OnlineEstimator(BASE)
    est.ingest(metrics=m, journal=j)
    snap = est.calibrated_params()
    assert snap.sample_counts["step"] == 10
    assert snap.sample_counts["sync"] == 1
    assert snap.sample_counts["detections"] == 2
    assert snap.sdc_fraction == pytest.approx(0.5)   # one hang, one SDC

    est.ingest(metrics=m, journal=j)                 # same data again
    again = est.calibrated_params()
    assert again.sample_counts == snap.sample_counts

    for _ in range(5):
        m.observe("sedar_stage_duration_seconds", 2.0, stage="train_step")
    est.ingest(metrics=m, journal=j)
    grown = est.calibrated_params()
    assert grown.sample_counts["step"] == 15
    assert grown.params.t_step * 3600.0 == pytest.approx(2.0)


def test_estimator_confidence_halved_without_sync_samples():
    est = OnlineEstimator(BASE)
    for _ in range(64):
        est.observe_step_s(2.0)
    assert est.calibrated_params().confidence == pytest.approx(0.5)
    est.observe_sync_s(4.0)
    assert est.calibrated_params().confidence == 1.0


def test_estimator_tier_costs_override_measured_only():
    est = OnlineEstimator(BASE)
    est.observe_tier_save_s("host", 1.0)
    est.observe_tier_restore_s("host", 2.0)
    snap = est.calibrated_params()
    assert snap.tier_costs["host"].t_save * 3600.0 == pytest.approx(1.0)
    assert snap.tier_costs["host"].t_restore * 3600.0 == pytest.approx(2.0)
    # unmeasured tiers keep the model defaults
    defaults = tm.default_tier_costs(BASE)
    assert snap.tier_costs["disk"] == defaults["disk"]


# ---------------------------------------------------------------------------
# anomaly detectors
# ---------------------------------------------------------------------------

def test_ewma_band_flags_spike_not_jitter():
    band = EwmaBand(k=4.0, warmup=8)
    rs = np.random.RandomState(3)
    fired = [band.update(1.0 + 0.01 * rs.randn()) for _ in range(50)]
    assert not any(fired)
    assert band.update(5.0)            # 400-sigma spike
    # the spike was excluded from the estimate, so normal traffic resumes
    assert not band.update(1.0)


def test_page_hinkley_detects_sustained_shift():
    ph = PageHinkley(delta=0.005, threshold=0.5)
    assert not any(ph.update(1.0) for _ in range(100))
    shifted = [ph.update(1.2) for _ in range(100)]
    assert any(shifted)


def test_cusum_two_sided():
    up, down = Cusum(warmup=8), Cusum(warmup=8)
    rs = np.random.RandomState(5)
    ref = [1.0 + 0.01 * rs.randn() for _ in range(8)]
    for v in ref:
        up.update(v)
        down.update(v)
    assert any(up.update(1.1) for _ in range(20))
    assert any(down.update(0.9) for _ in range(20))


def test_anomaly_monitor_streams_and_fired_log():
    mon = AnomalyMonitor()
    for _ in range(10):
        assert mon.update("fault_rate", 0.0) == []
    out = mon.update("fault_rate", 6.0)
    assert out and out[0]["stream"] == "fault_rate"
    assert out[0]["detector"] in ("ewma_band", "cusum")
    assert mon.fired[-len(out):] == out
    # an independent stream is unaffected
    assert mon.update("step_time", 2.0) == []


# ---------------------------------------------------------------------------
# alerts + SLO burn
# ---------------------------------------------------------------------------

def test_alert_manager_dedup_and_journal_roundtrip():
    obs.enable_metrics()
    j = FaultJournal()
    obs.set_journal(j)
    mgr = AlertManager(min_interval_steps=16)
    a = Alert(name="step_time_drift", severity="warning", step=0,
              message="m", detail={"value": 1.5})
    assert mgr.emit(a)
    assert not mgr.emit(Alert(name="step_time_drift", severity="warning",
                              step=10, message="m2"))       # held down
    assert mgr.emit(Alert(name="step_time_drift", severity="warning",
                          step=32, message="m3"))           # re-alerts
    assert mgr.emit(Alert(name="slo_goodput", severity="critical",
                          step=10, message="m4"))           # distinct name
    assert len(mgr.records) == 3
    assert obs.metrics.get("sedar_alerts_total", alert="step_time_drift",
                           severity="warning") == 2
    # byte-for-byte: journaled alert payloads == manager's record list
    verdict = obs.reconcile(j.records(), [], [], alerts=mgr.records)
    assert verdict["alerts_match"]
    verdict = obs.reconcile(j.records(), [], [], alerts=mgr.records[:-1])
    assert not verdict["alerts_match"]


def test_slo_tracker_multi_window_burn():
    slo = SloTracker("availability", target=0.99, fast_window=4,
                     slow_window=8)
    step = 0
    for _ in range(8):                     # healthy: no burn
        assert slo.update(step, 1.0) is None
        step += 1
    alerts = []
    for _ in range(4):                     # hard outage fills the fast window
        alerts.append(slo.update(step, 0.0))
        step += 1
    fired = [a for a in alerts if a is not None]
    assert fired and fired[0].name == "slo_availability"
    assert fired[0].severity == "critical"
    assert fired[0].detail["fast_burn"] >= 14.0
    # at the default-scale fast window, one bad sample must NOT page:
    # err 1/32 burns ~3x, far below the 14x fast gate
    slo2 = SloTracker("availability", target=0.99, fast_window=32,
                      slow_window=64)
    for s in range(40):
        assert slo2.update(s, 1.0) is None
    assert slo2.update(40, 0.0) is None


# ---------------------------------------------------------------------------
# engine.apply_reconfig safety semantics
# ---------------------------------------------------------------------------

def test_apply_reconfig_refused_mid_window(tmp_workdir):
    eng = _toy_engine(tmp_workdir, lag=8, ckpt_interval=100)
    dual = eng.init_dual()
    eng.reset()
    for s in range(3):                     # partial window: ring non-empty
        out = eng.run_protected_step(
            dual, jnp.full((16,), float(s + 1), jnp.float32), s)
        dual = out.dual
    assert eng.pending_validation
    assert eng.apply_reconfig(validate_lag=2, reason="mid") is None
    assert eng.validate_lag == 8
    ev = eng.flush_deferred()              # clean boundary
    assert ev is None and not eng.pending_validation
    rec = eng.apply_reconfig(validate_lag=2, reason="boundary")
    assert rec is not None
    assert rec["changes"] == {"validate_lag": {"from": 8, "to": 2}}
    assert eng.validate_lag == 2 and eng.schedule.validate_lag == 2


def test_apply_reconfig_clamps_and_noops(tmp_workdir, monkeypatch):
    eng = _toy_engine(tmp_workdir, lag=4)
    # no-op change: nothing journaled, nothing recorded
    assert eng.apply_reconfig(validate_lag=4) is None
    assert eng.reconfigs == []
    # an executor without deferred support clamps any request to lag 1
    monkeypatch.setattr(eng.executor, "supports_deferred", False,
                        raising=False)
    rec = eng.apply_reconfig(validate_lag=64, reason="clamp")
    assert rec["changes"]["validate_lag"] == {"from": 4, "to": 1}
    assert eng.validate_lag == 1


def test_apply_reconfig_checkpoint_interval_and_reset(tmp_workdir):
    eng = _toy_engine(tmp_workdir, lag=4, ckpt_interval=3)
    j = FaultJournal()
    obs.set_journal(j)
    rec = eng.apply_reconfig(validate_lag=8, checkpoint_interval=7,
                             reason="retune")
    assert set(rec["changes"]) == {"validate_lag", "checkpoint_interval"}
    assert eng.schedule.checkpoint_interval == 7
    if hasattr(eng.recovery, "interval"):
        assert eng.recovery.interval == 7
    # journaled byte-for-byte
    verdict = obs.reconcile(j.records(), [], [], reconfigs=eng.reconfigs)
    assert verdict["reconfigs_match"]
    # reset() restores the configured baseline (no knob leaks across runs)
    eng.reset()
    assert eng.validate_lag == 4
    assert eng.schedule.checkpoint_interval == 3
    assert eng.reconfigs == []


def test_autotune_one_shot_replans_from_snapshot():
    est = OnlineEstimator(BASE)
    _calibrate_storm(est)
    snap = est.calibrated_params()
    eng = _StubEngine(lag=8)
    rec = autotune(eng, snap, mode="train")
    want = tm.optimal_validate_lag(snap.params, snap.mtbe_hours)
    assert want != 8, "storm calibration should move the optimum off 8"
    assert rec is not None
    assert eng.validate_lag == want
    assert "autotune[train]" in rec["reason"]
    # already optimal: a second call is a no-op
    assert autotune(eng, snap, mode="train") is None


# ---------------------------------------------------------------------------
# Autotuner hysteresis + burst override
# ---------------------------------------------------------------------------

def test_autotuner_persistence_gates_flap():
    cfg = AutotuneConfig(interval_steps=1, persistence=3,
                         min_confidence=0.0)
    tuner = Autotuner(BASE, cfg)
    _calibrate_storm(tuner.estimator)
    eng = _StubEngine(lag=8)
    assert tuner.maybe_tune(eng, 1) is None       # vote 1 of 3
    assert tuner.maybe_tune(eng, 2) is None       # vote 2 of 3
    rec = tuner.maybe_tune(eng, 3)                # vote 3: applied
    assert rec is not None and eng.validate_lag != 8
    assert len(eng.reconfigs) == 1


def test_autotuner_low_confidence_is_advisory_only():
    cfg = AutotuneConfig(interval_steps=1, persistence=1,
                         min_confidence=0.25)
    tuner = Autotuner(BASE, cfg)
    # storm-grade MTBE but almost no step samples: confidence ~0
    t = 0.0
    for _ in range(6):
        tuner.estimator.observe_fault(t)
        t += 72.0
    eng = _StubEngine(lag=8)
    for step in range(1, 5):
        assert tuner.maybe_tune(eng, step) is None
    assert eng.validate_lag == 8 and eng.reconfigs == []
    assert tuner.evaluations == 4                 # it still watched


def test_autotuner_burst_overrides_persistence():
    """A fault-rate change-point CONFIRMS the environment shift, so the
    retarget lands without waiting out the persistence votes."""
    cfg = AutotuneConfig(interval_steps=1, persistence=50,
                         min_confidence=0.0)
    tuner = Autotuner(BASE, cfg)
    for _ in range(64):
        tuner.estimator.observe_step_s(2.0)
    tuner.estimator.observe_sync_s(4.0)
    eng = _StubEngine(lag=8)
    # quiet evaluations warm the fault-rate detectors at zero faults and
    # (calm optimum == big lag != 8) pile up pending votes far below 50
    for step in range(1, 10):
        assert tuner.maybe_tune(eng, step) is None
    assert eng.reconfigs == []
    # the storm arrives between two evaluations: a burst of detections
    t = 0.0
    for _ in range(12):
        tuner.estimator.observe_fault(t)
        t += 72.0
    rec = tuner.maybe_tune(eng, 10)
    assert tuner._last_det_count == 12
    assert rec is not None, "burst must bypass the persistence wait"
    assert eng.validate_lag == tm.optimal_validate_lag(
        tuner.estimator.calibrated_params().params,
        tuner.estimator.calibrated_params().mtbe_hours)
    assert not tuner._burst                       # consumed by the apply


def test_autotuner_backend_advice_is_an_alert_not_a_swap():
    cfg = AutotuneConfig(interval_steps=1, persistence=10**6,
                         min_confidence=0.0, backend="sequential")
    tuner = Autotuner(BASE, cfg)
    _calibrate_storm(tuner.estimator)
    eng = _StubEngine(lag=8)
    tuner.maybe_tune(eng, 1)
    names = [a["name"] for a in tuner.alerts.records]
    snap = tuner.estimator.calibrated_params()
    dup = tm.aet_strategy(snap.params, "detection", snap.mtbe_hours,
                          X=cfg.X_expected)
    abft = tm.aet_strategy(snap.params, "abft", snap.mtbe_hours,
                           X=cfg.X_expected)
    if abft < dup:                   # advice only fires when ABFT wins
        assert "backend_advice" in names
        adv = next(a for a in tuner.alerts.records
                   if a["name"] == "backend_advice")
        assert adv["severity"] == "info"
        assert adv["detail"]["recommended"] == "abft"
    assert eng.reconfigs == []       # advisory: no knob was touched


# ---------------------------------------------------------------------------
# a shifting fault environment: calm, then a storm
# ---------------------------------------------------------------------------

T_STEP_S, T_SYNC_S = 2.0, 4.0
PHASES = ((1600, 8.0), (600, 0.02))       # (steps, MTBE hours)


def _fault_steps(seed=0):
    """Steps at which a fault commits: exponential gaps per phase, one
    trace shared by every policy."""
    rs = np.random.RandomState(seed)
    faults, offset = set(), 0
    for steps, mtbe_h in PHASES:
        gap = mtbe_h * 3600.0 / T_STEP_S
        t = rs.exponential(gap)
        while t < steps:
            faults.add(offset + int(t))
            t += rs.exponential(gap)
        offset += steps
    return faults


def _simulated_wall(faults, lag, tuner=None):
    """Deferred validation at `lag` (or as `tuner` retunes it), costed per
    step, per flush, and per step a detection discards (fault commit ->
    the flush that surfaces it): the trade Eq. (11) prices. The tuner's
    estimator is fed what a running engine would measure: jittered step
    times, flush times and back-dated fault times."""
    rs = np.random.RandomState(1)
    eng = _StubEngine(lag=lag)
    wall, step, last_flush, pending = 0.0, 0, 0, []
    for steps, _ in PHASES:
        for _ in range(steps):
            step += 1
            dt = T_STEP_S * (1.0 + 0.05 * rs.randn())
            wall += dt
            if tuner is not None:
                tuner.estimator.observe_step_s(dt)
            if step - 1 in faults:
                pending.append(step)
            eng.pending_validation = step - last_flush < eng.validate_lag
            if not eng.pending_validation:
                wall += T_SYNC_S
                if tuner is not None:
                    tuner.estimator.observe_sync_s(T_SYNC_S)
                if pending:
                    wall += (step - min(pending) + 1) * T_STEP_S
                    if tuner is not None:
                        for fs in pending:
                            tuner.estimator.observe_fault(
                                wall - (step - fs) * T_STEP_S)
                    pending.clear()
                last_flush = step
            if tuner is not None:
                tuner.maybe_tune(eng, step)
    return wall, eng


def test_autotuner_converges_after_mtbe_shift():
    """The lag the Autotuner lands on after the storm is within one ladder
    step of the calibrated optimum, it calibrates the step time within 5%
    under 5% jitter, and its run costs no more than any fixed lag's on the
    same fault trace."""
    base = dataclasses.replace(BASE, t_step=T_STEP_S / 3600.0,
                               t_sync=T_SYNC_S / 3600.0)
    faults = _fault_steps()
    tuner = Autotuner(base, AutotuneConfig(interval_steps=16, persistence=2,
                                           min_confidence=0.25))
    wall, eng = _simulated_wall(faults, 8, tuner)
    snap = tuner.estimator.calibrated_params()
    want = tm.optimal_validate_lag(snap.params, snap.mtbe_hours)
    ladder = list(tm.LAG_CANDIDATES)
    assert eng.reconfigs
    assert abs(ladder.index(eng.validate_lag) - ladder.index(want)) <= 1
    assert abs(snap.params.t_step * 3600.0 - T_STEP_S) / T_STEP_S < 0.05
    fixed = {lag: _simulated_wall(faults, lag)[0] for lag in ladder}
    assert wall <= min(fixed.values()), (wall, fixed)


# ---------------------------------------------------------------------------
# end-to-end: toy engine retuned at a clean flush boundary
# ---------------------------------------------------------------------------

def test_toy_engine_autotune_reconfigs_at_boundary(tmp_workdir):
    obs.enable_metrics()
    j = FaultJournal()
    obs.set_journal(j)
    eng = _toy_engine(tmp_workdir, lag=4, ckpt_interval=100)
    tuner = Autotuner(BASE, AutotuneConfig(interval_steps=4, persistence=1,
                                           min_confidence=0.0))
    dual = eng.init_dual()
    eng.reset()
    for s in range(12):
        out = eng.run_protected_step(
            dual, jnp.full((16,), float(s + 1), jnp.float32), s)
        dual = out.dual
        assert out.event is None
        tuner.maybe_tune(eng, s + 1)
    assert eng.reconfigs, "an eval must land on an empty ring within 12 steps"
    rec = eng.reconfigs[0]
    want = tm.optimal_validate_lag(
        tuner.estimator.calibrated_params().params,
        tuner.estimator.calibrated_params().mtbe_hours)
    assert eng.validate_lag == want
    assert rec["changes"]["validate_lag"]["from"] == 4
    # every alert and reconfig reconstructs byte-for-byte from the journal
    verdict = obs.reconcile(j.records(), eng.detections, eng.recoveries,
                            alerts=tuner.alerts.records,
                            reconfigs=eng.reconfigs)
    assert verdict == {"detections_match": True, "recoveries_match": True,
                       "alerts_match": True, "reconfigs_match": True}
    assert obs.metrics.get("sedar_reconfigs_total", knob="validate_lag") \
        == len(eng.reconfigs)


# ---------------------------------------------------------------------------
# the zero-extra-hostsync acceptance criterion
# ---------------------------------------------------------------------------

def test_autotune_on_adds_zero_host_syncs_train(tmp_workdir):
    """Fault-free lag-8 window: count_transfers label maps with the full
    autotune loop ticking (estimator ingest + watch every 2 steps) must
    EQUAL the autotune-off maps — the control loop reads only host-side
    aggregates. Persistence is set high so no knob change fires inside the
    counted window (an applied lag change legitimately moves the flush
    cadence; that path is covered above)."""
    LAG = 8

    def run(workdir, tuner):
        eng = _toy_engine(workdir, lag=LAG, ckpt_interval=100)
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
                if tuner is not None:
                    tuner.maybe_tune(eng, s + 1)
        assert eng.validate_lag == LAG
        return st

    off = run(tmp_workdir + "_off", None)
    obs.enable_metrics()
    obs.set_journal(FaultJournal())
    tuner = Autotuner(BASE, AutotuneConfig(interval_steps=2,
                                           persistence=10**6))
    on = run(tmp_workdir + "_on", tuner)
    assert tuner.evaluations >= 4
    assert on.by_label == off.by_label == {"deferred_flush": 1}


def test_autotune_on_serve_same_transfer_labels():
    """Same contract through the continuous-batching loop at lag 8."""
    from repro.configs import RunConfig, TrainConfig, get_config, \
        reduce_for_smoke
    from repro.runtime.scheduler import synthetic_requests
    from repro.runtime.serve import SedarServer

    rc = RunConfig(model=reduce_for_smoke(get_config("qwen2-0.5b")),
                   train=TrainConfig(global_batch=2, seq_len=8))
    params = SedarServer(rc, dual=True).model.init(jax.random.PRNGKey(0))

    def reqs():
        return synthetic_requests(5, arrival_rate=2.0, prompt_lengths=(4, 8),
                                  max_new_choices=(4, 8), seed=1)

    def run(tuner):
        srv = SedarServer(rc, dual=True)
        srv.serve(params, reqs(), slots=3, validate_lag=8)  # warm jit cache
        with hostsync.count_transfers() as st:
            _, rep = srv.serve(params, reqs(), slots=3, validate_lag=8,
                               autotune=tuner)
        assert not rep.detections
        return st

    off = run(None)
    obs.enable_metrics()
    obs.set_journal(FaultJournal())
    tuner = Autotuner(BASE, AutotuneConfig(interval_steps=4,
                                           persistence=10**6, mode="serve"))
    on = run(tuner)
    assert tuner.evaluations >= 1
    assert on.by_label == off.by_label, (on.by_label, off.by_label)
