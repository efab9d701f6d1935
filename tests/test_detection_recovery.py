"""End-to-end SEDAR: inject -> detect -> recover, per protection level.

These are the system-level analogues of the paper's Sec. 4.2 experiments:
the recovered trajectory must be bitwise identical to a fault-free run."""
import dataclasses
import gc
import shutil
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (RunConfig, SedarConfig, TrainConfig, get_config,
                           reduce_for_smoke)
from repro.core.injection import InjectionSpec
from repro.data import SyntheticLM
from repro.runtime.train import SedarTrainer

CFG = reduce_for_smoke(get_config("paper-testapp"))
TRAIN = TrainConfig(global_batch=4, seq_len=16, steps=10, warmup_steps=2,
                    lr=1e-3)


def _trainer(workdir, level, inj=None, data=None, **sedar_kw):
    kw = dict(level=level, replication="sequential", validate_interval=1,
              param_validate_interval=4, checkpoint_interval=4,
              toe_timeout_s=60.0)
    kw.update(sedar_kw)
    rc = RunConfig(model=CFG, train=TRAIN, sedar=SedarConfig(**kw))
    return SedarTrainer(rc, workdir, inj_spec=inj, data=data)


def _clean_fp(workdir, data=None):
    tr = _trainer(workdir + "_clean", 1, data=data)
    _, rep = tr.run(10)
    assert not rep.detections
    return rep.final_state_fp


def test_l1_detects_and_stops(tmp_workdir):
    spec = InjectionSpec(leaf_idx=3, flat_idx=5, bit=20, step=4, replica=1,
                         target="grads")
    tr = _trainer(tmp_workdir, 1, inj=spec)
    _, rep = tr.run(10)
    assert rep.stopped                                  # safe stop
    assert rep.detections and rep.detections[0].step == 4
    assert rep.detections[0].boundary == "commit"      # pre-send validation


def test_l3_tdc_single_rollback_bitexact(tmp_workdir):
    clean = _clean_fp(tmp_workdir)
    spec = InjectionSpec(leaf_idx=3, flat_idx=5, bit=20, step=5, replica=1,
                         target="grads")
    tr = _trainer(tmp_workdir, 3, inj=spec)
    _, rep = tr.run(10)
    assert len(rep.detections) == 1
    assert rep.recoveries[0]["kind"] == "restore"
    assert rep.recoveries[0]["rollbacks"] == 1          # Alg. 2: at most one
    assert np.array_equal(rep.final_state_fp[:, :2], clean[:, :2])


def test_recovery_drops_the_pre_recovery_state(tmp_workdir):
    """The training loop holds no reference to the state a recovery
    replaced: the next step runs with the restored state and the ring
    copy alone (a third stacked copy is what does not fit the chip)."""
    spec = InjectionSpec(leaf_idx=3, flat_idx=5, bit=20, step=2, replica=1,
                         target="grads")
    tr = _trainer(tmp_workdir, 2, inj=spec, replication="fused",
                  ckpt_tiers="device", device_ring_slots=1)
    run_step = tr.engine.run_protected_step
    replaced, checked = [], []

    def watched(dual, batch, step):
        gc.collect()
        if replaced:
            checked.append([r() is None for r in replaced])
            replaced.clear()
        out = run_step(dual, batch, step)
        if out.event is not None:
            replaced.extend(weakref.ref(x) for x in jax.tree.leaves(out.dual))
        return out

    tr.engine.run_protected_step = watched
    _, rep = tr.run(4)
    assert [e.step for e in rep.detections] == [2]
    assert checked and all(all(c) for c in checked), checked


def test_l2_dirty_checkpoint_double_rollback(tmp_workdir):
    """FSC corruption in a never-touched embedding row: grad compare stays
    silent, the checkpoint cut after the fault is DIRTY, and Algorithm 1
    needs two rollbacks (paper Fig. 2b / scenario 50)."""
    data = SyntheticLM(vocab_size=200, global_batch=4, seq_len=16, seed=0)
    clean = _clean_fp(tmp_workdir, data=data)
    D = CFG.d_model
    spec = InjectionSpec(leaf_idx=1, flat_idx=250 * D + 3, bit=22, step=4,
                         replica=1, target="params")
    tr = _trainer(tmp_workdir, 2, inj=spec, data=data,
                  checkpoint_interval=3, param_validate_interval=8)
    _, rep = tr.run(10)
    assert [e.effect for e in rep.detections] == ["FSC", "FSC"]
    assert [r["rollbacks"] for r in rep.recoveries] == [1, 2]
    assert rep.recoveries[0]["step"] == 6               # dirty ckpt
    assert rep.recoveries[1]["step"] == 3               # clean ckpt
    assert np.array_equal(rep.final_state_fp[:, :2], clean[:, :2])


def test_le_dead_data_not_detected(tmp_workdir):
    """LE: corrupt a gradient row whose update is identical anyway? No —
    true LE is dead data. Corrupting replica-1's *optimizer v* for an unused
    row decays but never propagates to grads; param-validate catches it as
    state divergence (FSC). A genuinely dead fault = injection armed for a
    step that never executes -> zero detections, results valid."""
    data = SyntheticLM(vocab_size=200, global_batch=4, seq_len=16, seed=0)
    clean = _clean_fp(tmp_workdir, data=data)
    spec = InjectionSpec(leaf_idx=1, flat_idx=3, bit=22, step=99, replica=1,
                         target="params")                # beyond the run: LE
    tr = _trainer(tmp_workdir, 3, inj=spec, data=data)
    _, rep = tr.run(10)
    assert not rep.detections
    assert np.array_equal(rep.final_state_fp[:, :2], clean[:, :2])


def test_toe_detected_and_recovered(tmp_workdir):
    tr = _trainer(tmp_workdir, 3, toe_timeout_s=0.5)
    tr.toe_delay = {(5, 1): 0.8}                        # replica 1 stalls
    _, rep = tr.run(10)
    assert any(e.boundary == "toe" for e in rep.detections)
    assert rep.steps_completed == 10                    # recovered, finished


def test_l3_single_valid_checkpoint_invariant(tmp_workdir):
    tr = _trainer(tmp_workdir, 3)
    _, rep = tr.run(10)
    store = tr.recovery.store
    assert len(store.steps()) == 1                      # exactly one retained
    assert store.manifest(store.steps()[0]).valid is True


def test_l2_chain_never_pruned(tmp_workdir):
    tr = _trainer(tmp_workdir, 2, checkpoint_interval=2)
    _, rep = tr.run(10)
    assert len(tr.recovery.store.steps()) == len(rep.checkpoints) >= 4


def test_injection_flag_prevents_reinjection(tmp_workdir):
    """Paper's injected.txt: after recovery, re-execution of the same step
    does NOT re-inject (otherwise L3 would loop forever)."""
    spec = InjectionSpec(leaf_idx=3, flat_idx=5, bit=20, step=5, replica=1,
                         target="grads")
    tr = _trainer(tmp_workdir, 3, inj=spec)
    _, rep = tr.run(10)
    assert len(rep.detections) == 1                     # fired exactly once
    assert rep.steps_completed == 10


def test_plain_mode_ignores_faults(tmp_workdir):
    """Unprotected baseline silently commits the corruption (the paper's
    motivating failure mode)."""
    data = SyntheticLM(vocab_size=200, global_batch=4, seq_len=16, seed=0)
    clean = _clean_fp(tmp_workdir, data=data)
    spec = InjectionSpec(leaf_idx=3, flat_idx=5, bit=20, step=5, replica=0,
                         target="grads")
    tr = _trainer(tmp_workdir, 1, inj=spec, data=data, replication="none")
    _, rep = tr.run(10)
    assert not rep.detections
    assert not np.array_equal(rep.final_state_fp[:, :2], clean[:, :2])
