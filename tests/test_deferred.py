"""Deferred validation window + fused executor + zero-sync hot path
(DESIGN.md §11).

Covers the acceptance properties of the device-resident protected step:
  * a fault-free protected step with validate_lag>=8 performs ZERO
    device->host transfers (asserted via the `hostsync.count_transfers`
    hook the whole engine/driver stack reports through);
  * a fault injected at step k with validate_lag=D is detected at step
    <= k+D, rolls back to a checkpoint <= k, and the replayed trajectory
    is bitwise-identical to a fault-free validate_lag=1 run of the same
    compiled program (the unfired twin of the fault's spec);
  * the fused (single-launch, vmapped) executor matches its own lag=1
    trajectory bitwise at any lag, and its commit gate keeps L0 retry
    working even with donated buffers;
  * the engine clamps the lag when recovery cannot rewind (L0 retry);
  * bounded-chain L2 GC retains one checkpoint older than the validation
    frontier (the deferred retention rule).
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (RunConfig, SedarConfig, TrainConfig, get_config,
                           reduce_for_smoke)
from repro.core import hostsync
from repro.core.detection import SedarSafeStop
from repro.core.engine import FusedSequentialExecutor
from repro.core.fingerprint import pytree_fingerprint, \
    pytree_fingerprint_fused
from repro.core.injection import InjectionSpec, MemoryInjectionFlag, \
    inject_tree
from repro.core.policy import make_engine
from repro.core.recovery import RetryRecovery
from repro.runtime.train import SedarTrainer


# -- toy workload (same shape as test_engine's) ------------------------------

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
                ckpt_interval=3, validate_interval=0):
    sedar = SedarConfig(level=level, replication=backend,
                        validate_interval=1, validate_lag=lag,
                        param_validate_interval=validate_interval,
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
    """The zero-sync driver loop (host-side step tracking, one resync per
    recovery) — the same shape SedarTrainer.run uses."""
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


def _x(eng, dual):
    return np.asarray(eng.executor.peek(dual, "x"))


SPEC = InjectionSpec(leaf_idx=0, flat_idx=5, bit=20, step=4, replica=1,
                     target="grads")


def _unfired(spec):
    """The fault's twin that never fires: `step` past any run. A fault-free
    reference built from it compiles the same injection hook, so it is the
    same program as the faulted run (the contract of DESIGN.md §11)."""
    return dataclasses.replace(spec, step=10 ** 6)


# -- zero-sync steady state ---------------------------------------------------

@pytest.mark.parametrize("backend", ["fused", "sequential"])
def test_zero_transfers_between_flushes(tmp_workdir, backend):
    """Acceptance: a fault-free protected step with validate_lag>=8 performs
    0 device->host transfers; the flush step performs exactly one."""
    eng = _toy_engine(tmp_workdir, 2, backend=backend, lag=8,
                      ckpt_interval=100)
    dual = eng.init_dual()
    eng.reset()
    # compile outside the counted region
    out = eng.run_protected_step(dual, jnp.ones((16,), jnp.float32), 0)
    dual = eng.init_dual()
    eng.reset()
    with hostsync.count_transfers() as st:
        for s in range(7):
            out = eng.run_protected_step(
                dual, jnp.full((16,), float(s + 1), jnp.float32), s)
            dual = out.dual
            assert out.event is None
    assert st.transfers == 0, st.by_label
    with hostsync.count_transfers() as st:
        out = eng.run_protected_step(dual, jnp.full((16,), 8.0, jnp.float32),
                                     7)
    assert out.event is None
    assert st.transfers == 1
    assert st.by_label == {"deferred_flush": 1}
    assert eng.validated_frontier == 8


@pytest.mark.parametrize("backend", ["fused", "sequential"])
def test_trainer_steps_sync_only_at_flushes(tmp_workdir, backend):
    """The same property through SedarTrainer on the paper test app: at
    validate_lag=8, the only readbacks of 16 fault-free steps are the two
    window flushes."""
    rc = RunConfig(
        model=reduce_for_smoke(get_config("paper-testapp")),
        train=TrainConfig(global_batch=2, seq_len=16, steps=16,
                          warmup_steps=2, lr=1e-3),
        sedar=SedarConfig(level=1, replication=backend, validate_interval=1,
                          validate_lag=8, param_validate_interval=0,
                          checkpoint_interval=0))
    tr = SedarTrainer(rc, tmp_workdir)
    eng = tr.engine
    batch = {k: jnp.asarray(v) for k, v in tr.data.batch(0).items()}
    eng.run_protected_step(tr.init_dual(), batch, 0)   # compile, uncounted
    dual = tr.init_dual()
    eng.reset()
    with hostsync.count_transfers() as st:
        for s in range(16):
            out = eng.run_protected_step(dual, batch, s)
            dual = out.dual
            assert out.event is None
    assert st.by_label == {"deferred_flush": 2}


def test_lag1_syncs_every_compare(tmp_workdir):
    """Control: the classic path reads the predicate back every step."""
    eng = _toy_engine(tmp_workdir, 2, backend="fused", lag=1,
                      ckpt_interval=100)
    dual = eng.init_dual()
    eng.reset()
    out = eng.run_protected_step(dual, jnp.ones((16,), jnp.float32), 0)
    dual = eng.init_dual()
    eng.reset()
    with hostsync.count_transfers() as st:
        for s in range(4):
            dual = eng.run_protected_step(
                dual, jnp.full((16,), float(s + 1), jnp.float32), s).dual
    assert st.by_label.get("commit_compare") == 4


# -- deferred detection / rollback / bitwise replay ---------------------------

@pytest.mark.parametrize("backend", ["fused", "sequential"])
@pytest.mark.parametrize("lag", [4, 8])
def test_deferred_fault_detected_within_window(tmp_workdir, backend, lag):
    """Fault at step k: detection fires at <= k+D, rollback lands on a
    checkpoint <= k, and the replayed trajectory is bitwise-identical to a
    fault-free validate_lag=1 run of the same backend and program."""
    k = SPEC.step
    eng = _toy_engine(tmp_workdir, 2, spec=SPEC, backend=backend, lag=lag,
                      ckpt_interval=3)
    dual, stopped = _drive(eng, 10)
    assert not stopped
    assert len(eng.detections) == 1
    ev = eng.detections[0]
    assert ev.boundary == "deferred" and ev.effect == "TDC"
    assert ev.step == k
    assert ev.detail["detected_at"] <= k + lag
    assert [r["kind"] for r in eng.recoveries] == ["restore"]
    assert eng.recoveries[0]["step"] <= k      # pre-fault checkpoint

    ref = _toy_engine(tmp_workdir + "_ref", 2, spec=_unfired(SPEC),
                      backend=backend, lag=1, ckpt_interval=3)
    dual_ref, _ = _drive(ref, 10)
    np.testing.assert_array_equal(_x(eng, dual), _x(ref, dual_ref))


@pytest.mark.parametrize("lag", [1, 8])
def test_fused_matches_itself_across_lags_clean(tmp_workdir, lag):
    """One compiled program serves both lag modes, so clean trajectories are
    bitwise-identical whatever the window size."""
    a = _toy_engine(tmp_workdir + "_a", 2, backend="fused", lag=lag)
    b = _toy_engine(tmp_workdir + "_b", 2, backend="fused", lag=32)
    da, _ = _drive(a, 9)
    db, _ = _drive(b, 9)
    np.testing.assert_array_equal(_x(a, da), _x(b, db))
    assert a.detections == [] and b.detections == []


def test_deferred_fault_near_end_caught_by_final_flush(tmp_workdir):
    """A fault inside the LAST (partial) window is still caught: the driver
    drains the ring before declaring the run complete."""
    spec = InjectionSpec(leaf_idx=0, flat_idx=5, bit=20, step=7, replica=1,
                         target="grads")
    eng = _toy_engine(tmp_workdir, 2, spec=spec, backend="fused", lag=32,
                      ckpt_interval=3)
    dual, stopped = _drive(eng, 8)
    assert not stopped
    assert [e.boundary for e in eng.detections] == ["deferred"]
    assert eng.detections[0].step == 7
    ref = _toy_engine(tmp_workdir + "_ref", 2, spec=_unfired(spec),
                      backend="fused", lag=1, ckpt_interval=3)
    dual_ref, _ = _drive(ref, 8)
    np.testing.assert_array_equal(_x(eng, dual), _x(ref, dual_ref))


def test_deferred_l1_safe_stops_on_flush(tmp_workdir):
    """L1 + deferred window: the flush event degrades to the safe stop —
    detection latency is <= D but no defective result is delivered."""
    eng = _toy_engine(tmp_workdir, 1, spec=SPEC, backend="fused", lag=4,
                      ckpt_interval=0)
    dual, stopped = _drive(eng, 10)
    assert stopped
    assert [r["kind"] for r in eng.recoveries] == ["stop"]
    assert eng.detections[0].step == SPEC.step


# -- fused executor semantics -------------------------------------------------

def test_fused_lag1_commit_gate_supports_retry(tmp_workdir):
    """Immediate mode: the in-jit gate returns pre-step values on mismatch,
    so L0 retry re-executes the same step even though buffers are donated."""
    eng = _toy_engine(tmp_workdir, 1, spec=SPEC, backend="fused", lag=1)
    eng.recovery = RetryRecovery(max_retries=4)
    dual, stopped = _drive(eng, 8)
    assert not stopped
    assert [e.boundary for e in eng.detections] == ["commit"]
    assert [r["kind"] for r in eng.recoveries] == ["retry"]
    ref = _toy_engine(tmp_workdir + "_ref", 1, spec=_unfired(SPEC),
                      backend="fused", lag=1)
    ref.recovery = RetryRecovery(max_retries=4)
    dual_ref, _ = _drive(ref, 8)
    np.testing.assert_array_equal(_x(eng, dual), _x(ref, dual_ref))


def test_fused_l3_validated_checkpoint_roundtrip(tmp_workdir):
    """L3 with the stacked representation: the engine checkpoints the
    primary view, restores a single state, and adopt_single re-stacks it."""
    eng = _toy_engine(tmp_workdir, 3, spec=SPEC, backend="fused", lag=1,
                      ckpt_interval=3)
    dual, stopped = _drive(eng, 8)
    assert not stopped
    assert [r["kind"] for r in eng.recoveries] == ["restore"]
    ref = _toy_engine(tmp_workdir + "_ref", 3, spec=_unfired(SPEC),
                      backend="fused", lag=1, ckpt_interval=3)
    dual_ref, _ = _drive(ref, 8)
    np.testing.assert_array_equal(_x(eng, dual), _x(ref, dual_ref))


def test_engine_clamps_lag_for_retry_recovery(tmp_workdir):
    """L0 retry cannot rewind past the current step, so the engine degrades
    validate_lag to 1 rather than letting a fault outlive its window."""
    eng = _toy_engine(tmp_workdir, 1, backend="fused", lag=16)
    eng2 = _toy_engine(tmp_workdir + "_b", 1, backend="fused", lag=16)
    assert eng.validate_lag == 16
    sedar = SedarConfig(level=1, replication="fused", validate_lag=16)
    eng3 = make_engine(sedar, backend="fused",
                       step_fn=_toy_step_fn(None),
                       state_fp_fn=jax.jit(
                           lambda s: pytree_fingerprint({"x": s["x"]})),
                       recovery=RetryRecovery(max_retries=2),
                       notify=lambda e: None)
    assert eng3.validate_lag == 1
    del eng2


def test_vote_backend_never_defers():
    """The NMR forward-repair protocol consumes the predicate immediately."""
    from repro.core.engine import VoteExecutor
    assert VoteExecutor.supports_deferred is False


# -- L2 retention rule --------------------------------------------------------

def test_gc_keeps_checkpoint_older_than_frontier(tmp_path):
    """Bounded-chain GC must retain >=1 version no newer than the validation
    frontier: a fault anywhere in the deferred window then always has a
    rollback target that predates it."""
    from repro.checkpoint import CheckpointStore
    store = CheckpointStore(str(tmp_path))
    state = {"x": np.arange(4, dtype=np.float32)}
    for s in (3, 6, 9, 12):
        store.save(s, state)
    # frontier = 5: steps >= 5 unvalidated; keep-last-2 alone would drop
    # every version <= 5, stranding faults at steps 5..8
    store.gc_keep_last(2, keep_floor=5)
    assert store.steps() == [3, 9, 12]
    # frontier newer than the whole chain: plain keep-last applies
    store.gc_keep_last(2, keep_floor=20)
    assert store.steps() == [9, 12]


def test_engine_passes_frontier_to_gc(tmp_workdir):
    """End-to-end: with max_checkpoints=1 and a deferred window, the chain
    keeps the frontier anchor alongside the newest version."""
    sedar = SedarConfig(level=2, replication="fused", validate_interval=1,
                        validate_lag=4, param_validate_interval=0,
                        checkpoint_interval=2, max_checkpoints=1,
                        checkpoint_dir=os.path.join(tmp_workdir, "ckpt"))
    state_fp = jax.jit(lambda s: pytree_fingerprint({"x": s["x"]}))

    def init_single():
        return {"x": jnp.zeros((16,), jnp.float32),
                "step": jnp.zeros((), jnp.int32)}

    eng = make_engine(sedar, backend="fused", workdir=tmp_workdir,
                      step_fn=_toy_step_fn(None), state_fp_fn=state_fp,
                      init_fn=lambda: eng.executor.init_dual(init_single()),
                      notify=lambda e: None)
    dual, stopped = _drive(eng, 8)
    assert not stopped
    store = eng.recovery.store
    # every checkpoint was cut after a clean flush, so the newest one always
    # predates the (empty) unvalidated window — the chain stays bounded and
    # rollback-complete
    assert store.steps() == [8]
    assert eng.validated_frontier == 8


@pytest.mark.parametrize("backend", ["fused", "sequential"])
def test_off_boundary_divergence_is_adopted_and_caught(tmp_workdir, backend):
    """With commit_interval=2, a fault on a NON-compared step must be
    ADOPTED (not silently reverted by the fused gate) so the next compare
    boundary sees the diverged updates and detection still fires."""
    spec = InjectionSpec(leaf_idx=0, flat_idx=5, bit=20, step=3, replica=1,
                         target="grads")          # step 3: compare not due
    sedar = SedarConfig(level=2, replication=backend, validate_interval=2,
                        param_validate_interval=0, checkpoint_interval=2,
                        checkpoint_dir=os.path.join(tmp_workdir, "ckpt"))
    state_fp = jax.jit(lambda s: pytree_fingerprint({"x": s["x"]}))

    def init_single():
        return {"x": jnp.zeros((16,), jnp.float32),
                "step": jnp.zeros((), jnp.int32)}

    eng = make_engine(sedar, backend=backend, workdir=tmp_workdir,
                      step_fn=_toy_step_fn(spec), state_fp_fn=state_fp,
                      inj_spec=spec, inj_flag=MemoryInjectionFlag(),
                      init_fn=lambda: eng.executor.init_dual(init_single()),
                      notify=lambda e: None)
    dual, stopped = _drive(eng, 8)
    assert not stopped
    # divergence adopted at 3, caught at the next commit boundary (step 4);
    # the checkpoint cut at 4 contains the divergence, so Alg. 1 walks the
    # dirty version first and lands on the clean one at 2
    assert [(e.step, e.boundary) for e in eng.detections] == \
        [(4, "commit"), (4, "commit")]
    assert [(r["kind"], r["step"]) for r in eng.recoveries] == \
        [("restore", 4), ("restore", 2)]
    assert int(np.asarray(eng.executor.peek(dual, "step"))) == 8
