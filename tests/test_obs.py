"""Unified observability layer (DESIGN.md §15): metrics registry, shared
percentile helper, fault journal, trace spans, KPIs, cluster gauges.

Also documents (as an executable spec) the `hostsync.TransferStats`
thread-local shim behavior: a scoped `count_transfers()` region counts only
the opening thread's readbacks, while the process-wide registry aggregates
across threads under its lock — the explicit cross-thread mode the shim
deliberately lacks."""
import json
import os
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.checkpoint import store as ckpt_store
from repro.core import hostsync
from repro.obs.journal import FaultJournal, _jsonable, canonical, \
    event_to_record
from repro.obs.kpi import compute_kpis, reconcile_with_advice
from repro.obs.registry import MetricsRegistry, percentile
from repro.obs.trace import TraceRecorder
from repro.runtime import prefill


@pytest.fixture(autouse=True)
def _obs_teardown():
    yield
    obs.shutdown()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_histograms():
    m = MetricsRegistry()
    m.inc("a_total")
    m.inc("a_total", 3)
    m.inc("a_total", 2, label="x")
    m.set_gauge("g", 7.5)
    m.set_gauge("g", 2.5)
    for v in (1.0, 2.0, 3.0, 4.0):
        m.observe("h_ms", v)
    assert m.get("a_total") == 4
    assert m.get("a_total", label="x") == 2
    assert m.get("g") == 2.5
    h = m.get_histogram("h_ms")
    assert h.count == 4 and h.total == 10.0
    assert h.quantile(50) == 2.0 and h.quantile(99) == 4.0
    assert m.get("never_touched") == 0.0


def test_registry_kind_conflict_rejected():
    m = MetricsRegistry()
    m.inc("x")
    with pytest.raises(ValueError):
        m.set_gauge("x", 1.0)


def test_registry_prometheus_render():
    m = MetricsRegistry()
    m.inc("req_total", 5, route="a")
    m.inc("req_total", 1, route="b")
    m.set_gauge("depth", 3)
    m.observe("lat_ms", 10.0)
    text = m.render_prometheus()
    assert "# TYPE req_total counter" in text
    assert 'req_total{route="a"} 5' in text
    assert 'req_total{route="b"} 1' in text
    assert "depth 3" in text
    assert "lat_ms_count 1" in text and "lat_ms_sum 10" in text
    # real Prometheus histogram exposition: cumulative le-labeled buckets
    assert "# TYPE lat_ms histogram" in text
    assert 'lat_ms_bucket{le="5"} 0' in text
    assert 'lat_ms_bucket{le="10"} 1' in text
    assert 'lat_ms_bucket{le="1000"} 1' in text
    assert 'lat_ms_bucket{le="+Inf"} 1' in text


def test_prometheus_histogram_roundtrip():
    """render_prometheus -> parse_prometheus is lossless for counters,
    gauges, and histogram bucket/sum/count samples (labels included)."""
    from repro.obs.registry import parse_prometheus
    m = MetricsRegistry()
    m.set_buckets("lat_s", (0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        m.observe("lat_s", v, stage="x")
    m.inc("req_total", 2, route="a")
    m.set_gauge("depth", 4)
    types, samples = parse_prometheus(m.render_prometheus())
    assert types == {"lat_s": "histogram", "req_total": "counter",
                     "depth": "gauge"}
    bucket = samples["lat_s_bucket"]
    assert bucket[(("le", "0.1"), ("stage", "x"))] == 1
    assert bucket[(("le", "1"), ("stage", "x"))] == 2
    assert bucket[(("le", "10"), ("stage", "x"))] == 3
    assert bucket[(("le", "+Inf"), ("stage", "x"))] == 4
    assert samples["lat_s_sum"][(("stage", "x"),)] == \
        pytest.approx(55.55)
    assert samples["lat_s_count"][(("stage", "x"),)] == 4
    assert samples["req_total"][(("route", "a"),)] == 2
    assert samples["depth"][()] == 4


def test_registry_cross_thread_aggregation():
    """The registry's explicit cross-thread mode: increments from worker
    threads land in the same series (lock-protected)."""
    m = MetricsRegistry()

    def work():
        for _ in range(500):
            m.inc("t_total")

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert m.get("t_total") == 2000


# ---------------------------------------------------------------------------
# percentile (satellite: one shared nearest-rank implementation)
# ---------------------------------------------------------------------------

def test_percentile_property_vs_numpy():
    """Nearest-rank must agree with numpy's inverted_cdf method over random
    sizes/quantiles (seeded property sweep)."""
    rs = np.random.RandomState(7)
    for _ in range(200):
        n = int(rs.randint(1, 60))
        vals = rs.rand(n) * rs.choice([1.0, 1e3, 1e-3])
        q = float(rs.choice([0.0, 1.0, 25.0, 50.0, 75.0, 90.0, 99.0, 100.0]))
        got = percentile(vals, q)
        want = float(np.percentile(vals, q, method="inverted_cdf"))
        assert got == want, (n, q, got, want)


def test_percentile_edges():
    assert percentile([], 50) == 0.0
    assert percentile([42.0], 99) == 42.0
    assert percentile([1, 2, 3, 4], 50) == 2.0     # true nearest-rank median
    assert percentile([1, 2, 3, 4], 99) == 4.0     # p99 clamps to max
    assert percentile([3, 1, 2], 0) == 1.0


def test_scheduler_percentiles_use_shared_helper():
    from repro.runtime.scheduler import Request, latency_percentiles_ms, \
        ttft_percentiles_ms
    reqs = []
    for rid in range(4):
        r = Request(rid=rid, prompt=np.zeros(4, np.int32), max_new_tokens=3)
        r.arrival_time = 0.0
        r.token_times = [0.010 * (rid + 1), 0.010 * (rid + 1) + 0.005]
        reqs.append(r)
    tt50, tt99 = ttft_percentiles_ms(reqs)
    lats = [r.token_times[0] for r in reqs]
    assert tt50 == pytest.approx(1e3 * percentile(lats, 50))
    assert tt99 == pytest.approx(1e3 * percentile(lats, 99))
    p50, p99 = latency_percentiles_ms(reqs)
    assert p50 == pytest.approx(5.0) and p99 == pytest.approx(5.0)
    assert ttft_percentiles_ms([]) == (0.0, 0.0)


def test_scheduler_ttlt_and_stream_stats():
    from repro.runtime.scheduler import Request, stream_stats_ms, \
        ttlt_latencies, ttlt_percentiles_ms
    reqs = []
    for rid in range(4):
        r = Request(rid=rid, prompt=np.zeros(4, np.int32), max_new_tokens=3)
        r.arrival_time = 0.0
        r.token_times = [0.010 * (rid + 1), 0.010 * (rid + 1) + 0.005]
        reqs.append(r)
    # TTLT = last token stamp - arrival, one sample per emitting request
    assert ttlt_latencies(reqs) == pytest.approx(
        [0.015, 0.025, 0.035, 0.045])
    tl50, tl99 = ttlt_percentiles_ms(reqs)
    lats = [r.token_times[-1] for r in reqs]
    assert tl50 == pytest.approx(1e3 * percentile(lats, 50))
    assert tl99 == pytest.approx(1e3 * percentile(lats, 99))
    assert ttlt_percentiles_ms([]) == (0.0, 0.0)
    # never-emitted requests are excluded, not zero samples
    ghost = Request(rid=9, prompt=np.zeros(4, np.int32), max_new_tokens=3)
    ghost.arrival_time = 0.0
    assert len(ttlt_latencies(reqs + [ghost])) == 4
    stats = stream_stats_ms(reqs)
    assert set(stats) == {"ttft_p50_ms", "ttft_p99_ms", "itl_p50_ms",
                          "itl_p99_ms", "ttlt_p50_ms", "ttlt_p99_ms"}
    assert stats["ttlt_p50_ms"] == tl50
    assert stats["itl_p50_ms"] == pytest.approx(5.0)


# ---------------------------------------------------------------------------
# legacy shim absorption
# ---------------------------------------------------------------------------

def test_metrics_absorb_hostsync_transfers():
    obs.enable_metrics()
    hostsync.read_scalar(jnp.asarray(1.0), label="probe")
    hostsync.batched_get([jnp.zeros(2), jnp.zeros(3)], label="pair")
    assert obs.metrics.get("hostsync_transfers_total", label="probe") == 1
    assert obs.metrics.get("hostsync_transfers_total", label="pair") == 2
    assert obs.metrics.get("hostsync_batches_total", label="pair") == 1


def test_metrics_off_is_noop():
    assert not obs.metrics_enabled()
    hostsync.read_scalar(jnp.asarray(1.0), label="probe")
    assert obs.metrics.snapshot() == {}
    # note_* intake is also inert with everything off
    obs.note_checkpoint(3)
    obs.note_tokens(5)
    assert obs.metrics.snapshot() == {}
    assert obs.get_journal() is None


def test_metrics_absorb_compiles_and_disk_reads():
    obs.enable_metrics()
    prefill._note_compile(("pack", 16, 2))
    prefill._note_compile(("pack", 32, 4))
    ckpt_store._note_disk_read("leaf", 3)
    ckpt_store._note_disk_read("manifest")
    assert obs.metrics.get("prefill_compiles_total", kind="pack") == 2
    assert obs.metrics.get("checkpoint_disk_reads_total", label="leaf") == 3
    assert obs.metrics.get("checkpoint_disk_reads_total",
                           label="manifest") == 1


def test_transfer_stats_thread_local_vs_registry():
    """Documents the shim contract: a count_transfers region on the main
    thread does NOT see a worker thread's readbacks (thread-local by
    design), but the registry DOES — the cross-thread aggregation mode."""
    obs.enable_metrics()
    done = threading.Event()

    def worker():
        hostsync.read_scalar(jnp.asarray(2.0), label="worker_read")
        done.set()

    with hostsync.count_transfers() as st:
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert done.is_set()
    assert st.transfers == 0, "shim must stay thread-local"
    assert st.by_label == {}
    assert obs.metrics.get("hostsync_transfers_total",
                           label="worker_read") == 1


def test_transfer_stats_cross_thread_region():
    """`count_transfers(cross_thread=True)` closes the thread-local blind
    spot: the scoped region counts readbacks issued by OTHER threads (the
    detokenize-drain consumer) while it is open — matching the registry —
    without changing the default thread-local contract."""
    done = threading.Event()

    def worker():
        hostsync.read_scalar(jnp.asarray(2.0), label="drain_read")
        hostsync.batched_get([jnp.zeros(2), jnp.zeros(3)],
                             label="drain_read")
        done.set()

    with hostsync.count_transfers(cross_thread=True) as xt, \
            hostsync.count_transfers() as local:
        hostsync.read_scalar(jnp.asarray(1.0), label="main_read")
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert done.is_set()
    # cross-thread region sees BOTH threads' readbacks
    assert xt.by_label == {"main_read": 1, "drain_read": 3}
    assert xt.batches == 3 and xt.transfers == 4
    # the plain region on the same thread stays thread-local
    assert local.by_label == {"main_read": 1}
    # deregistration: readbacks after the region close are not counted
    hostsync.read_scalar(jnp.asarray(3.0), label="late_read")
    assert "late_read" not in xt.by_label


def test_transfer_stats_cross_thread_nests_with_registry():
    """All three views are independent: thread-local region, cross-thread
    region, and the metrics registry each see their own scope."""
    obs.enable_metrics()

    def worker():
        hostsync.read_scalar(jnp.asarray(1.0), label="w")

    with hostsync.count_transfers(cross_thread=True) as xt:
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert xt.by_label == {"w": 1}
    assert obs.metrics.get("hostsync_transfers_total", label="w") == 1


# ---------------------------------------------------------------------------
# journal
# ---------------------------------------------------------------------------

def test_journal_roundtrip_and_canonical(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = FaultJournal(path)
    j.append("detection", step=np.int64(4),
             event={"step": np.int32(4), "detail": {3: np.float32(1.5),
                                                    "arr": np.arange(2)}})
    j.append("recovery", step=2, record={"kind": "restore", "at": 5})
    j.close()
    loaded = FaultJournal.load(path)
    assert [r["kind"] for r in loaded] == ["detection", "recovery"]
    assert loaded[0]["seq"] == 0 and loaded[1]["seq"] == 1
    assert loaded[0]["t_mono"] <= loaded[1]["t_mono"]
    # byte-for-byte: in-memory records equal their disk round trip
    for mem, disk in zip(j.entries, loaded):
        assert canonical(mem) == canonical(disk)
    # numpy scalars and int keys normalized identically on both sides
    assert loaded[0]["event"]["detail"]["3"] == 1.5
    assert loaded[0]["event"]["detail"]["arr"] == [0, 1]


def test_jsonable_normalizes_like_json():
    obj = {"a": np.int32(1), "b": (np.float64(2.0), np.bool_(True)),
           5: np.arange(3), "n": None}
    norm = _jsonable(obj)
    assert norm == json.loads(json.dumps(norm))


def test_event_to_record_and_reconcile():
    from repro.core.detection import DetectionEvent
    evs = [DetectionEvent(step=3, boundary="deferred", effect="TDC",
                          detail={"detected_at": 7, "lag": 4})]
    recs = [{"kind": "restore", "step": 2, "rollbacks": 1, "at": 3}]
    j = FaultJournal()
    for e in evs:
        j.append("detection", step=e.step, event=event_to_record(e))
    for r in recs:
        j.append("recovery", step=r["step"], record=r)
    verdict = obs.reconcile(j.records(), evs, recs)
    assert verdict == {"detections_match": True, "recoveries_match": True}
    verdict = obs.reconcile(j.records(), evs, [dict(recs[0], at=9)])
    assert not verdict["recoveries_match"]


def test_journal_fsync_cadence_and_explicit_sync(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = FaultJournal(path, fsync_every=2)
    j.append("checkpoint", step=1)
    assert j.synced_seq == -1              # first append only flushed
    j.append("checkpoint", step=2)
    assert j.synced_seq == 1               # cadence hit: both on disk
    j.append("checkpoint", step=3)
    assert j.synced_seq == 1
    j.sync()
    assert j.synced_seq == 2
    j.close()
    assert [r["step"] for r in FaultJournal.load(path)] == [1, 2, 3]


def test_journal_survives_torn_final_line(tmp_path):
    """Crash regression: a kill -9 mid-write leaves a torn last line; the
    loader must return every complete record and skip the fragment."""
    path = str(tmp_path / "j.jsonl")
    j = FaultJournal(path, fsync_every=1)
    for s in range(3):
        j.append("detection", step=s, event={"step": s})
    # simulate the crash: the file handle is abandoned (no close()) and the
    # next process finds a half-written line at the tail
    j._fh.write('{"kind": "detection", "seq": 3, "tr')
    j._fh.flush()
    j._fh = None                           # drop without close/atexit flush
    loaded = FaultJournal.load(path)
    assert [r["step"] for r in loaded] == [0, 1, 2]
    assert all(r["kind"] == "detection" for r in loaded)


def test_journal_rotation_preserves_full_stream(tmp_path):
    """Size rotation keeps ONE prior generation; across a single rotation
    `load()` still reconstructs the full stream in order (the documented
    bounded-campaign contract)."""
    path = str(tmp_path / "j.jsonl")
    j = FaultJournal(path, max_bytes=2048)
    s = 0
    while not os.path.exists(path + ".1"):     # fill to the first rotation
        j.append("checkpoint", step=s)
        s += 1
        assert s < 200, "rotation never triggered"
    for _ in range(3):                         # a short tail generation
        j.append("checkpoint", step=s)
        s += 1
    j.close()
    loaded = FaultJournal.load(path)
    assert [r["seq"] for r in loaded] == list(range(s))
    assert [r["step"] for r in loaded] == list(range(s))
    for mem, disk in zip(j.entries, loaded):
        assert canonical(mem) == canonical(disk)


# ---------------------------------------------------------------------------
# KPIs under elastic events (fail-in-place, DESIGN.md §16)
# ---------------------------------------------------------------------------

def test_kpi_elastic_remesh_not_counted_as_sdc_recovery():
    """An elastic_remesh recovery pairs with the heartbeat anomaly that
    triggered it — never with an SDC detection line — so `mttr_s` and
    `elastic_mttr_s` stay independent."""
    j = FaultJournal()
    j.append("detection", step=5,
             event={"step": 5, "boundary": "deferred", "effect": "TDC",
                    "detail": {"detected_at": 7, "lag": 4}})
    j.append("heartbeat_anomaly", host=2, gap_s=30.0, anomaly="stale")
    j.append("recovery", step=6,
             record={"kind": "elastic_remesh", "step": 6, "at": 8,
                     "downtime_s": 2.0})
    j.append("recovery", step=5,
             record={"kind": "restore", "step": 5, "rollbacks": 1, "at": 7})
    recs = j.records()
    k = compute_kpis(recs, steps=20, wall_s=100.0)
    assert k["detections"] == 1 and k["recoveries"] == 2
    assert k["elastic_remeshes"] == 1
    assert k["node_loss_downtime_s"] == pytest.approx(2.0)
    # the SDC restore pairs with the detection (seq 3 - seq 0)...
    assert k["mttr_s"] == pytest.approx(recs[3]["t_mono"] -
                                        recs[0]["t_mono"])
    # ...and the remesh pairs with the heartbeat anomaly (seq 2 - seq 1)
    assert k["elastic_mttr_s"] == pytest.approx(recs[2]["t_mono"] -
                                                recs[1]["t_mono"])
    # redone work folds in from BOTH; downtime additionally scales uptime
    assert k["redone_steps"] == (8 - 6) + (7 - 5)
    assert k["availability"] == pytest.approx((1 - 4 / 20) * (1 - 2 / 100))


def test_kpi_shrink_then_regrow_replay():
    """A shrink + regrow campaign replayed from the journal: each remesh
    claims its own heartbeat anomaly, none double-pair, and with no SDC
    detections the SDC MTTR stays zero."""
    j = FaultJournal()
    j.append("heartbeat_anomaly", host=3, gap_s=45.0, anomaly="stale")
    j.append("recovery", step=10,
             record={"kind": "elastic_remesh", "step": 10, "at": 12,
                     "direction": "shrink", "downtime_s": 1.0})
    j.append("heartbeat_anomaly", host=3, gap_s=0.0, anomaly="rejoin")
    j.append("recovery", step=20,
             record={"kind": "elastic_remesh", "step": 20, "at": 20,
                     "direction": "regrow", "downtime_s": 0.5})
    recs = j.records()
    k = compute_kpis(recs, steps=40, wall_s=200.0)
    assert k["detections"] == 0
    assert k["mttr_s"] == 0.0              # nothing SDC-shaped to pair
    assert k["elastic_remeshes"] == 2
    assert k["node_loss_downtime_s"] == pytest.approx(1.5)
    # each remesh claimed the anomaly immediately preceding it
    want = ((recs[1]["t_mono"] - recs[0]["t_mono"]) +
            (recs[3]["t_mono"] - recs[2]["t_mono"])) / 2
    assert k["elastic_mttr_s"] == pytest.approx(want)


def test_journal_replay_groups():
    j = FaultJournal()
    j.append("detection", step=1)
    j.append("rejection", step=2, rid=7)
    j.append("detection", step=3)
    groups = obs.replay(j.records())
    assert len(groups["detection"]) == 2
    assert groups["rejection"][0]["rid"] == 7


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------

def test_trace_spans_chrome_format(tmp_path):
    tr = TraceRecorder()
    with tr.span("decode_tick", step=3):
        with tr.span("validate"):
            pass
    path = str(tmp_path / "trace.json")
    tr.write(path)
    with open(path) as fh:
        doc = json.load(fh)
    names = [e["name"] for e in doc["traceEvents"]]
    assert names == ["validate", "decode_tick"]   # inner span closes first
    for e in doc["traceEvents"]:
        assert e["ph"] == "X" and e["dur"] >= 0 and e["ts"] >= 0
    assert doc["traceEvents"][1]["args"]["step"] == 3


def test_span_lands_in_the_profiler_host_plane(tmp_path):
    """Every program span is also a profiler annotation: a JAX profile
    taken while tracing is on holds it by name, on the device ops' clock."""
    import glob
    import sys

    import jax
    from jax.profiler import ProfileData

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from perf.trace_reduce import host_events

    tr = obs.enable_trace()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("x", step=1):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    [(start, end)] = host_events(ProfileData.from_file(path), "x")
    assert end > start
    # the recorder's Chrome-trace event is unchanged by the annotation
    [ev] = tr.by_name("x")
    assert ev["args"] == {"step": 1} and ev["ph"] == "X"


SERVE_STAGES = ("serve_start", "admit", "slot_snapshot", "slot_release",
                "token_deliver", "serve_finish")


def test_serve_spans_cover_the_host_stages_of_a_call():
    """A fused lag-8 serve() names each host stage in a span of its own:
    no span lasts as long as the call, and the main thread's outermost
    spans cover nearly all of the call's host time."""
    import time

    import jax
    from repro.configs import RunConfig, TrainConfig, get_config, \
        reduce_for_smoke
    from repro.runtime.scheduler import Request
    from repro.runtime.serve import SedarServer

    rc = RunConfig(model=reduce_for_smoke(get_config("qwen2-0.5b")),
                   train=TrainConfig(global_batch=2, seq_len=8))
    srv = SedarServer(rc, backend="fused")
    params = srv.model.init(jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)

    def backlog():
        # due at once, more requests than slots, budgets from 1 up: slots
        # free and refill mid-call, and a one-token budget releases at once
        return [Request(rid=i, prompt=rs.randint(0, 200, (L,)).astype(
                    np.int32), max_new_tokens=n)
                for i, (L, n) in enumerate([(8, 12), (4, 1), (8, 20),
                                            (4, 9), (8, 6), (4, 14)])]

    srv.serve(params, backlog(), slots=3, validate_lag=8)   # compile
    tr = obs.enable_trace()
    a = time.monotonic()
    out, rep = srv.serve(params, backlog(), slots=3, validate_lag=8)
    b = time.monotonic()
    assert len(rep.completed) == len(out) and not rep.detections

    assert set(SERVE_STAGES) <= {e["name"] for e in tr.events}
    assert {e["args"]["at"] for e in tr.by_name("slot_snapshot")} == {
        "admit", "flush"}
    call_us = (b - a) * 1e6
    assert max(e["dur"] for e in tr.events) < 0.5 * call_us
    main = threading.get_ident() & 0xFFFF
    lo, hi = (a - tr._t0) * 1e6, (b - tr._t0) * 1e6
    covered, reach = 0.0, lo
    for e in sorted((e for e in tr.events if e["tid"] == main),
                    key=lambda e: e["ts"]):
        s, f = max(e["ts"], reach), min(e["ts"] + e["dur"], hi)
        if f > s:
            covered += f - s
            reach = f
    assert covered >= 0.9 * call_us, covered / call_us


def test_global_span_noop_until_enabled():
    ctx = obs.span("anything")
    with ctx:
        pass
    assert obs.get_trace() is None
    tr = obs.enable_trace()
    with obs.span("real", step=1):
        pass
    assert [e["name"] for e in tr.by_name("real")] == ["real"]


# ---------------------------------------------------------------------------
# note_* intake + KPIs
# ---------------------------------------------------------------------------

def test_note_functions_feed_metrics_and_journal():
    from repro.core.detection import DetectionEvent
    obs.enable_metrics()
    j = FaultJournal()
    obs.set_journal(j)
    ev = DetectionEvent(step=4, boundary="commit", effect="TDC", detail={})
    obs.note_detection(ev)
    obs.note_recovery({"kind": "restore", "step": 2, "rollbacks": 1,
                       "at": 4, "tier": "device"})
    obs.note_recovery({"kind": "retry", "step": None, "rollbacks": 0,
                       "at": 5})
    obs.note_checkpoint(6)
    obs.note_tier_save("host")
    obs.note_tier_restore("device", 3)
    obs.note_tier_event({"kind": "tier_fallback", "tier": "disk",
                         "version": 2, "error": "X"})
    obs.note_rejection(7, rid=1, slot=0, reason="persistent_fault")
    obs.note_tokens(3)
    m = obs.metrics
    assert m.get("sedar_detections_total", boundary="commit",
                 effect="TDC") == 1
    assert m.get("sedar_recoveries_total", kind="restore") == 1
    assert m.get("sedar_recoveries_total", kind="retry") == 1
    assert m.get("sedar_rollbacks_total") == 1
    assert m.get("sedar_retries_total") == 1
    assert m.get("sedar_checkpoints_total") == 1
    assert m.get("checkpoint_saves_total", tier="host") == 1
    assert m.get("checkpoint_restores_total", tier="device") == 1
    assert m.get("checkpoint_tier_fallbacks_total", tier="disk") == 1
    assert m.get("serve_rejections_total", reason="persistent_fault") == 1
    assert m.get("serve_tokens_emitted_total") == 3
    kinds = [r["kind"] for r in j.records()]
    assert kinds == ["detection", "recovery", "recovery", "checkpoint",
                     "tier_restore", "tier_fallback", "rejection"]


def test_compute_kpis_and_reconcile():
    j = FaultJournal()
    j.append("detection", step=3,
             event={"step": 3, "boundary": "deferred", "effect": "TDC",
                    "detail": {"detected_at": 7, "lag": 4}})
    j.append("recovery", step=2,
             record={"kind": "restore", "step": 2, "rollbacks": 1, "at": 3})
    j.append("detection", step=10,
             event={"step": 10, "boundary": "commit", "effect": "TDC",
                    "detail": {}})
    j.append("recovery", step=10,
             record={"kind": "retry", "step": None, "rollbacks": 0,
                     "at": 10})
    k = compute_kpis(j.records(), steps=20, tokens=40, injected=2)
    assert k["detections"] == 2 and k["recoveries"] == 2
    assert k["mttd_steps"] == pytest.approx(2.0)   # (4 + 0) / 2
    assert k["mttd_max_steps"] == 4.0
    assert k["redone_steps"] == 1                  # restore: 3 - 2
    assert k["availability"] == pytest.approx(1 - 1 / 20)
    assert k["goodput_tokens_per_step"] == pytest.approx(2.0)
    assert k["sdc_coverage"] == 1.0
    assert k["mttr_s"] >= 0.0
    rows = reconcile_with_advice(k, validate_lag=8)
    by_metric = {r["metric"]: r for r in rows}
    assert by_metric["mttd_max_steps"]["ok"]
    assert by_metric["sdc_coverage"]["ok"]
    rows = reconcile_with_advice(k, validate_lag=2)
    assert not [r for r in rows if r["metric"] == "mttd_max_steps"][0]["ok"]


# ---------------------------------------------------------------------------
# cluster gauges + heartbeat anomalies (satellite)
# ---------------------------------------------------------------------------

def test_cluster_monitor_publish(tmp_path):
    from repro.runtime.cluster import ClusterMonitor, Heartbeat
    obs.enable_metrics()
    j = FaultJournal()
    obs.set_journal(j)
    hb_dir = str(tmp_path / "hb")
    for host, step in ((0, 10), (1, 10), (2, 2)):
        Heartbeat(hb_dir, host).beat(step)
    mon = ClusterMonitor(hb_dir, n_hosts=4, timeout_s=60.0,
                         straggler_factor=2.0)
    import time as _time
    summary = mon.publish(now=_time.time())
    assert summary["stale"] == [3]            # host 3 never beat
    assert summary["stragglers"] == [2]
    m = obs.metrics
    assert m.get("cluster_hosts_seen") == 3
    assert m.get("cluster_hosts_expected") == 4
    assert m.get("cluster_stale_hosts") == 1
    assert m.get("cluster_stragglers") == 1
    assert m.get("cluster_host_step", host=2) == 2
    anomalies = j.records("heartbeat_anomaly")
    assert {(a["host"], a["anomaly"]) for a in anomalies} == \
        {(3, "stale"), (2, "straggler")}
    assert m.get("cluster_heartbeat_anomalies_total", kind="stale") == 1


# ---------------------------------------------------------------------------
# launcher bundle
# ---------------------------------------------------------------------------

def test_configure_finalize_writes_artifacts(tmp_path):
    mdir = str(tmp_path / "metrics")
    tpath = str(tmp_path / "trace.json")
    ob = obs.configure(metrics_dir=mdir, trace=tpath)
    assert obs.metrics_enabled() and obs.get_journal() is not None
    with obs.span("train_step", step=0):
        pass
    obs.note_checkpoint(4)
    snap = ob.finalize()
    assert "sedar_checkpoints_total 1" in snap
    with open(mdir + "/metrics.prom") as fh:
        assert fh.read() == snap
    loaded = FaultJournal.load(mdir + "/journal.jsonl")
    assert [r["kind"] for r in loaded] == ["checkpoint"]
    with open(tpath) as fh:
        assert [e["name"] for e in json.load(fh)["traceEvents"]] == \
            ["train_step"]
    assert obs.get_journal() is None   # finalize detaches the journal


# ---------------------------------------------------------------------------
# live status view (DESIGN.md §17)
# ---------------------------------------------------------------------------

def test_status_render_consolidates_run_artifacts(tmp_path):
    from repro.launch.status import render
    mdir = str(tmp_path / "metrics")
    ob = obs.configure(metrics_dir=mdir)
    for _ in range(4):
        with obs.span("train_step", step=0):
            pass
    obs.note_checkpoint(6)
    obs.note_alert({"name": "step_time_drift", "severity": "warning",
                    "step": 8, "message": "band fired", "detail": {}})
    obs.note_reconfig({"kind": "reconfig", "step": 12, "reason": "autotune",
                       "changes": {"validate_lag": {"from": 4, "to": 16}}})
    ob.finalize()
    page = render(mdir)
    assert "journal: 3 records" in page
    assert "train_step" in page and "n=4" in page
    assert "step_time_drift" in page and "band fired" in page
    assert "validate_lag: 4->16" in page and "autotune" in page
    assert "optimal validate lag" in page      # the calibrated-model line


def test_status_render_empty_dir_is_graceful(tmp_path):
    from repro.launch.status import render
    page = render(str(tmp_path))
    assert "journal: empty" in page
