"""The readers of the idle time that the serving loop's host spans name,
on hand-built reductions: the value, and what each reads where the span it
needs is missing."""
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import run as bench_run  # noqa: E402
from perf.trace_reduce import NO_SPAN, Reduction  # noqa: E402


def reader(name):
    return bench_run.load_module(
        os.path.join(ROOT, "perf", "metrics", name + ".py"),
        "test_reader_" + name.replace(".", "_"))


def data(idle, spans=(), devices=1, window_s=20.0):
    red = Reduction(window_s=window_s, busy_s=window_s - sum(idle.values()),
                    devices=devices, idle_by_span_s=dict(idle))
    return SimpleNamespace(reduction=red,
                           spans=[(n, 0.0, 1.0, {}) for n in spans])


IDLE = {NO_SPAN: 0.3, "slot_snapshot": 1.0, "admit": 0.5,
        "prefill_pack": 0.1, "decode_tick": 0.2}
SPANS = ("serve_start", "decode_tick", "admit", "prefill_pack",
         "slot_snapshot")


@pytest.mark.parametrize("name,value", [
    ("unattributed_idle_pct.serve", 1.5),
    ("snapshot_idle_pct.serve", 5.0),
    ("admit_idle_pct.serve", 3.0),
])
def test_reader_value(name, value):
    assert reader(name).read(data(IDLE, SPANS)) == pytest.approx(value)


@pytest.mark.parametrize("name,missing,value", [
    # every idle stretch fell in some span
    ("unattributed_idle_pct.serve", NO_SPAN, 0.0),
    # no snapshot taken (the `none` backend) or none under an idle stretch
    ("snapshot_idle_pct.serve", "slot_snapshot", 0.0),
    ("admit_idle_pct.serve", "admit", 0.5),
])
def test_reader_span_took_no_idle(name, missing, value):
    idle = {k: v for k, v in IDLE.items() if k != missing}
    spans = [s for s in SPANS if s != missing]
    assert reader(name).read(data(idle, spans)) == pytest.approx(value)


@pytest.mark.parametrize("name", ["snapshot_idle_pct.serve",
                                  "admit_idle_pct.serve"])
def test_reader_without_stage_spans_reads_nothing(name):
    # a program that opens only the older spans around the decode tick
    # and the prefill launch
    spans = ("decode_tick", "prefill_pack", "deferred_flush")
    assert reader(name).read(data(IDLE, spans)) is None


@pytest.mark.parametrize("name", ["unattributed_idle_pct.serve",
                                  "snapshot_idle_pct.serve",
                                  "admit_idle_pct.serve"])
def test_reader_without_a_device_plane_reads_nothing(name):
    assert reader(name).read(data({}, SPANS, devices=0)) is None
