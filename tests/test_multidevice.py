"""Multi-device tests (sharding resolver, pod-backend SEDAR, dry-run smoke).

These need >1 device, so each test runs a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count set BEFORE jax import
(the main pytest process must keep seeing 1 device for the smoke tests)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_resolver_sharding_and_fallbacks():
    out = _run("""
import jax
from repro.launch.mesh import make_test_mesh
from repro.sharding import Resolver, ShardingRules
mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
r = Resolver(mesh, ShardingRules(data_axes=("data",)))
# heads divisible -> model axis on heads
s = r.spec(("embed", "heads", "head_dim"), (8, 4, 16), "wq")
assert "model" in str(s) and "data" in str(s), s
# heads NOT divisible -> falls through to head_dim
s2 = r.spec(("embed", "heads", "head_dim"), (8, 3, 16), "wq_bad")
assert s2[1] is None and any(f.logical == "heads" for f in r.fallbacks), s2
# batch_dm grabs data*model together when divisible
s3 = r.spec(("batch_dm", None, None), (4, 5, 7), "act")
assert s3[0] == ("data", "model"), s3
# batch_dm falls back to plain data when not divisible by data*model
s4 = r.spec(("batch_dm", None, None), (2, 5, 7), "act2")
assert s4[0] == "data", s4
print("resolver OK")
""")
    assert "resolver OK" in out


def test_pod_backend_sedar_detection():
    """Replicas on the pod axis: injected fault detected via the shard_map
    fingerprint exchange; commit gated; recovery completes."""
    out = _run("""
import jax, numpy as np, jax.numpy as jnp
from repro.configs import (RunConfig, SedarConfig, TrainConfig, get_config,
                           reduce_for_smoke)
from repro.core.injection import InjectionSpec
from repro.launch.mesh import make_test_mesh
from repro.runtime.train import SedarTrainer
mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = reduce_for_smoke(get_config("paper-testapp"))
rc = RunConfig(model=cfg,
               train=TrainConfig(global_batch=4, seq_len=16, steps=8,
                                 warmup_steps=2, lr=1e-3),
               sedar=SedarConfig(level=3, replication="pod",
                                 validate_interval=1,
                                 param_validate_interval=4,
                                 checkpoint_interval=4))
spec = InjectionSpec(leaf_idx=3, flat_idx=5, bit=20, step=5, replica=1,
                     target="grads")
import shutil; shutil.rmtree("/tmp/sedar_pod_test", ignore_errors=True)
with mesh:
    tr = SedarTrainer(rc, "/tmp/sedar_pod_test", mesh=mesh, inj_spec=spec)
    dual, rep = tr.run(8)
assert len(rep.detections) == 1 and rep.detections[0].step == 5, rep.detections
assert rep.recoveries[0]["kind"] == "restore"
assert rep.steps_completed == 8
# per-shard lane localization (DESIGN.md 16): the event names the lane the
# corrupted element hashes into, and the host owning that data shard
from repro.core.fingerprint import lane_of_leaf_index
grads_tree = jax.tree.map(np.asarray, tr.init_state()["params"])
lane = lane_of_leaf_index(grads_tree, 3, 5, 2)
assert rep.detections[0].detail.get("lanes") == [lane], rep.detections[0].detail
assert rep.detections[0].detail.get("hosts") == [lane], rep.detections[0].detail
print("pod backend OK", rep.summary())
""", devices=8, timeout=600)
    assert "pod backend OK" in out


def test_pod_backend_zero_sync_fault_free():
    """DESIGN.md 11 extended to spatial replication: the cross-replica
    compare happens via collectives INSIDE the jitted step, so a fault-free
    deferred-window run never reads the commit predicate back per step."""
    out = _run("""
import jax, numpy as np, jax.numpy as jnp
from repro.configs import (RunConfig, SedarConfig, TrainConfig, get_config,
                           reduce_for_smoke)
from repro.core import hostsync
from repro.launch.mesh import make_test_mesh
from repro.runtime.train import SedarTrainer
mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = reduce_for_smoke(get_config("paper-testapp"))
rc = RunConfig(model=cfg,
               train=TrainConfig(global_batch=4, seq_len=16, steps=16,
                                 warmup_steps=2, lr=1e-3),
               sedar=SedarConfig(level=3, replication="pod",
                                 validate_interval=1, validate_lag=4,
                                 param_validate_interval=100,
                                 checkpoint_interval=8,
                                 ckpt_tiers="device,partner"))
import shutil; shutil.rmtree("/tmp/sedar_pod_zs", ignore_errors=True)
with mesh:
    tr = SedarTrainer(rc, "/tmp/sedar_pod_zs", mesh=mesh)
    with hostsync.count_transfers() as st:
        dual, rep = tr.run(16)
assert not rep.detections
assert rep.steps_completed == 16
assert "commit_compare" not in st.by_label, st.by_label
assert st.by_label.get("deferred_flush", 0) <= 16 // 4 + 2, st.by_label
print("zero-sync pod OK", rep.summary())
""", devices=8, timeout=600)
    assert "zero-sync pod OK" in out


def test_pod_elastic_fail_in_place_acceptance():
    """The issue's acceptance scenario: 8-device replicated mesh, host loss
    mid-run -> automatic shrink with the anchor restored from the Tier-3
    partner store onto the survivors, regrow when the host returns, final
    state bitwise identical to an uninterrupted run — and zero fault-free
    commit-predicate readbacks throughout."""
    out = _run("""
import json, os, shutil
import jax, numpy as np
from repro.configs import (MeshConfig, RunConfig, SedarConfig, TrainConfig,
                           get_config, reduce_for_smoke)
from repro.core import hostsync
from repro.launch.mesh import make_test_mesh
from repro.runtime.elastic import ElasticTrainer
from repro.runtime.train import SedarTrainer

mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = reduce_for_smoke(get_config("paper-testapp"))
rc = RunConfig(model=cfg,
               train=TrainConfig(global_batch=4, seq_len=16, steps=12,
                                 warmup_steps=2, lr=1e-3),
               mesh=MeshConfig(shape=(2, 2, 2),
                               axis_names=("pod", "data", "model")),
               sedar=SedarConfig(level=3, replication="pod",
                                 validate_interval=1, validate_lag=4,
                                 param_validate_interval=100,
                                 checkpoint_interval=4,
                                 ckpt_tiers="device,partner"))
base = "/tmp/sedar_pod_elastic"
shutil.rmtree(base, ignore_errors=True)

with mesh:
    ref = SedarTrainer(rc, base + "/ref", mesh=mesh)
    _, ref_rep = ref.run(12)
assert not ref_rep.detections

wd = base + "/run"
hb = os.path.join(wd, "heartbeats")
sim = {"now": 0.0}

def tick(step):
    sim["now"] += 100.0
    os.makedirs(hb, exist_ok=True)
    for h in range(2):
        if h == 1 and 250.0 <= sim["now"] < 550.0:   # host 1 dark mid-run
            continue
        with open(os.path.join(hb, f"host_{h:05d}.json"), "w") as f:
            json.dump({"host": h, "step": int(step or 0), "t": sim["now"]}, f)

et = ElasticTrainer(rc, wd, mesh=mesh, n_hosts=2, scan_interval=2,
                    clock=lambda: sim["now"], tick=tick)
with hostsync.count_transfers() as st:
    rep = et.run(12)
phases = [r.phase for r in rep.remeshes]
assert phases == ["shrink", "regrow"], phases
assert rep.remeshes[0].restore_tier == "partner", rep.remeshes[0]
assert rep.remeshes[0].new_data == 1 and rep.remeshes[0].new_batch == 2
assert rep.steps_completed == 12 and not rep.stopped
assert np.array_equal(np.asarray(rep.final_state_fp)[:, :2],
                      np.asarray(ref_rep.final_state_fp)[:, :2])
assert "commit_compare" not in st.by_label, st.by_label
print("pod elastic OK", rep.summary())
""", devices=8, timeout=600)
    assert "pod elastic OK" in out


def test_dryrun_cell_small_arch():
    """Full dry-run machinery on the production 512-device mesh for the
    smallest assigned arch (lower+compile+memory+cost+collectives)."""
    out = _run("""
import repro.launch.dryrun as dr
cell = dr.run_cell("xlstm-125m", "decode_32k", "single", "baseline",
                   "/tmp/dryrun_test", with_probes=False)
assert cell["status"] == "ok", cell.get("error")
assert cell["memory"]["fits_16GiB"]
assert cell["roofline"]["dominant"] in ("compute", "memory", "collective")
print("dryrun OK", cell["roofline"]["dominant"])
""", devices=512, timeout=600)
    assert "dryrun OK" in out


def test_vote_mode_forward_correction():
    """Beyond-paper NMR: 3 replicas, state corrupted on one pod, majority
    vote repairs it forward (no rollback) and training completes."""
    out = _run("""
import jax, numpy as np, jax.numpy as jnp
from repro.configs import (RunConfig, SedarConfig, TrainConfig, get_config,
                           reduce_for_smoke)
from repro.core.injection import InjectionSpec
from repro.launch.mesh import make_test_mesh
from repro.runtime.train import SedarTrainer
mesh = make_test_mesh((3, 2, 1), ("pod", "data", "model"))
cfg = reduce_for_smoke(get_config("paper-testapp"))
rc = RunConfig(model=cfg,
               train=TrainConfig(global_batch=4, seq_len=16, steps=8,
                                 warmup_steps=2, lr=1e-3),
               sedar=SedarConfig(level=3, replication="vote",
                                 validate_interval=1,
                                 param_validate_interval=2,
                                 checkpoint_interval=100))
spec = InjectionSpec(leaf_idx=2, flat_idx=3, bit=30, step=3, replica=1,
                     target="params")
import shutil; shutil.rmtree("/tmp/sedar_vote_test", ignore_errors=True)
with mesh:
    tr = SedarTrainer(rc, "/tmp/sedar_vote_test", mesh=mesh, inj_spec=spec)
    dual, rep = tr.run(8)
assert any(r["kind"] == "vote_repair" for r in rep.recoveries), rep.recoveries
assert all(r["rollbacks"] == 0 for r in rep.recoveries)
assert rep.steps_completed == 8
print("vote OK", rep.summary())
""", devices=6, timeout=600)
    assert "vote OK" in out


def test_loopaware_collective_parser():
    out = _run("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.dryrun import (parse_collective_bytes,
                                 parse_collective_bytes_loopaware)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"), devices=jax.devices())
def step(w, x):
    def body(c, wl):
        h = jnp.einsum('bd,de->be', c, wl)
        return jax.lax.with_sharding_constraint(
            h, NamedSharding(mesh, P("data", None))), None
    out, _ = jax.lax.scan(body, x, w)
    return jnp.mean(out ** 2)
with mesh:
    comp = jax.jit(step, in_shardings=(
        NamedSharding(mesh, P(None, "model", None)),
        NamedSharding(mesh, P("data", None)))).lower(
        jax.ShapeDtypeStruct((5, 64, 64), jnp.float32),
        jax.ShapeDtypeStruct((8, 64), jnp.float32)).compile()
naive = parse_collective_bytes(comp.as_text())["total_bytes"]
loop = parse_collective_bytes_loopaware(comp.as_text())["total_bytes"]
# the in-loop all-reduce must be counted ~5x (trip count), not once
assert loop > 3 * naive, (naive, loop)
print("parser OK", naive, loop)
""", devices=8)
    assert "parser OK" in out
