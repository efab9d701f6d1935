"""The trace reduction against hand counts (a written trace) and against a
small trace recorded on a v5e (`perf/testdata/small.xplane.pb`)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns
PS_PER_MS = 1_000_000_000


def _line(name, ts_ns, events, lid=1):
    evs = "".join(f"events {{ metadata_id: {m} offset_ps: {int(a * PS_PER_MS)}"
                  f" duration_ps: {int((b - a) * PS_PER_MS)} }}\n"
                  for m, a, b in events)
    return f'lines {{ id: {lid} name: "{name}" timestamp_ns: {ts_ns}\n{evs}}}\n'


def _plane(pid, name, line, names):
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(names, 1))
    return f'planes {{ id: {pid} name: "{name}"\n{line}{meta}}}\n'


def written_trace():
    """Device 0: A [0,5] ms, B [4,6] (overlaps A), C [10,11] and a
    fingerprint kernel [20,30]. Device 1: one op [0,16]. Host: outer
    [0,40], decode_tick [6.5,9.5], prefill_pack [12,19]."""
    from jax.profiler import ProfileData
    dev0 = _plane(1, "/device:TPU:0", _line("XLA Ops", 0, [
        (1, 0, 5), (2, 4, 6), (3, 10, 11), (4, 20, 30)])
        + _line("XLA Modules", 0, [(5, 0, 11), (6, 20, 30)], lid=2),
        ["%fusion.1 = f32[8] fusion(%p)", "copy.7", "fusion.2",
         "%custom-call.3 = f32[8] custom-call(%x), kernel=sedar_fingerprint",
         "jit_step(123)", "jit_fn(456)"])
    dev1 = _plane(2, "/device:TPU:1", _line("XLA Ops", 0, [(1, 0, 16)]),
                  ["convolution.4"])
    host = _plane(3, "/host:CPU", _line("python", 0, [
        (1, 0, 40), (2, 6.5, 9.5), (3, 12, 19)]),
        ["outer", "decode_tick", "prefill_pack"])
    return ProfileData.from_text_proto(dev0 + dev1 + host)


def test_busy_idle_and_per_op_time_match_hand_counts():
    pd = written_trace()
    spans = [("outer", 0, 40 * MS), ("decode_tick", 6.5 * MS, 9.5 * MS),
             ("prefill_pack", 12 * MS, 19 * MS)]
    red = tr.reduce(pd, 0, 32 * MS, spans)
    assert red.devices == 2
    assert red.window_s == pytest.approx(0.032)
    # device 0 busy 6 + 1 + 10 = 17 ms, device 1 busy 16 ms: mean 16.5
    assert red.busy_s == pytest.approx(0.0165)
    assert red.idle_pct == pytest.approx(100 * (1 - 16.5 / 32))
    assert red.ops_s["fusion.1"] == pytest.approx(0.005 / 2)
    assert red.ops_s["fusion.2"] == pytest.approx(0.001 / 2)
    assert red.ops_s["copy.7"] == pytest.approx(0.002 / 2)
    assert red.ops_s["custom-call.3"] == pytest.approx(0.010 / 2)
    assert red.op_seconds("sedar_fingerprint") == pytest.approx(0.010 / 2)
    assert red.op_seconds("no_such_kernel") is None
    assert red.programs_s == {"jit_step": pytest.approx(0.011 / 2),
                              "jit_fn": pytest.approx(0.010 / 2)}


def test_idle_gaps_go_to_the_innermost_host_span():
    pd = written_trace()
    spans = [("outer", 0, 40 * MS), ("decode_tick", 6.5 * MS, 9.5 * MS),
             ("prefill_pack", 12 * MS, 19 * MS)]
    red = tr.reduce(pd, 0, 32 * MS, spans)
    # device 0: decode_tick 3 ms, prefill_pack 7 ms, outer 0.5+0.5+1+1+2;
    # device 1 (idle 16..32): prefill_pack 3 ms, outer 13 ms; both halved
    assert red.idle_by_span_s["decode_tick"] == pytest.approx(0.003 / 2)
    assert red.idle_by_span_s["prefill_pack"] == pytest.approx(0.010 / 2)
    assert red.idle_by_span_s["outer"] == pytest.approx((0.005 + 0.013) / 2)
    assert sum(red.idle_by_span_s.values()) == pytest.approx(
        red.window_s - red.busy_s)
    assert red.top_idle(1)[0][0] == "outer"
    no_spans = tr.reduce(pd, 0, 32 * MS)
    assert no_spans.idle_by_span_s == {
        tr.NO_SPAN: pytest.approx(red.window_s - red.busy_s)}


def test_window_clips_events_and_an_empty_window_has_no_devices():
    pd = written_trace()
    red = tr.reduce(pd, 25 * MS, 35 * MS)
    assert red.devices == 1                       # device 1 ends at 16 ms
    assert red.busy_s == pytest.approx(0.005)     # fingerprint 25..30
    assert red.programs_s == {"jit_fn": pytest.approx(0.005)}
    assert tr.reduce(pd, 50 * MS, 60 * MS).devices == 0
    with pytest.raises(ValueError):
        tr.reduce(pd, 5, 5)


def test_union_and_complement():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == \
        [(0, 3), (5, 8)]
    assert tr.complement([(0, 3), (5, 8)], -1, 10) == \
        [(-1, 0), (3, 5), (8, 10)]
    assert tr.op_name("%fusion.123 = bf16[2] fusion(%a)") == "fusion.123"
    assert tr.op_name("copy.4") == "copy.4"


def test_recorded_v5e_trace():
    """Three matmul programs, a 50 ms host sleep in `host_wait` between the
    second and the third (perf/testdata/record_trace.py)."""
    from jax.profiler import ProfileData
    path = os.path.join(ROOT, "perf", "testdata", "small.xplane.pb")
    pd = ProfileData.from_file(path)
    (start, end), = tr.host_events(pd, "perf_traced_call")
    spans = [("host_wait", s, e) for s, e in tr.host_events(pd, "host_wait")]
    assert len(spans) == 1
    red = tr.reduce(pd, start, end, spans)
    assert red.devices == 1
    assert 0 < red.busy_s < red.window_s
    assert 0.045 <= red.idle_by_span_s["host_wait"] <= 0.06
    assert red.ops_s and all(v > 0 for v in red.ops_s.values())
    assert sum(red.ops_s.values()) >= red.busy_s * 0.999
    assert set(red.programs_s) == {"jit__lambda"}
    assert red.programs_s["jit__lambda"] == pytest.approx(red.busy_s, rel=0.01)
