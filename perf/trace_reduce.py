"""From a profiler trace (xplane) to device busy time, per-op time and
attributed idle gaps.

    red = reduce(ProfileData.from_file(path), start_ns, end_ns, host_spans)

* Device planes are the planes named `/device:TPU:<n>`; their operations
  are the events of the line `XLA Ops`. Busy time is the union of those
  events' intervals inside the window, per device, averaged over the
  devices that ran anything.
* Per-op time is the summed duration of the events of one HLO op inside
  the window, averaged over the same devices. An event's name is the op's
  HLO text; the op is named by what precedes ` = ` (`%fusion.12 = ...` ->
  `fusion.12`). A loop or conditional op's time holds its body's ops. A
  kernel is found by a name anywhere in the HLO text (`op_seconds`).
* Per-program time is the summed duration of the events of the line
  `XLA Modules` (one per launch of a compiled program), by the program's
  name with its fingerprint dropped (`jit_step(1234)` -> `jit_step`),
  averaged over the same devices.
* An idle gap is a stretch of the window in which a device runs nothing.
  Each stretch of a gap is given to the innermost host span that covers
  it (the spans come with the call, on the trace's clock), or to
  `(no host span)`; the seconds are summed per name and averaged over
  devices.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "(no host span)"

Interval = Tuple[float, float]


@dataclass
class Reduction:
    window_s: float
    busy_s: float                 # mean over devices
    devices: int
    ops_s: Dict[str, float] = field(default_factory=dict)    # mean over devices
    text_s: Dict[str, float] = field(default_factory=dict)   # by HLO text
    programs_s: Dict[str, float] = field(default_factory=dict)
    idle_by_span_s: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def op_seconds(self, needle: str) -> Optional[float]:
        """Device seconds of every op whose HLO text holds `needle`, or
        None when the window holds none."""
        hits = [s for n, s in self.text_s.items() if needle in n]
        return sum(hits) if hits else None

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.ops_s.items(),
                                           key=lambda kv: -kv[1])[:n]]

    def top_idle(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.idle_by_span_s.items(),
                                           key=lambda kv: -kv[1])[:n]]


def op_name(text: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`."""
    return text.split(" = ", 1)[0].lstrip("%")


def program_name(name: str) -> str:
    """`jit_step(98765)` -> `jit_step`."""
    return name.split("(", 1)[0]


def device_ops(pd, line_name: str = OPS_LINE
               ) -> Dict[str, List[Tuple[str, float, float]]]:
    """{plane name: [(event name, start_ns, end_ns)]} of one line of every
    device plane."""
    out: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        evs = []
        for line in plane.lines:
            if line.name == line_name:
                evs.extend((e.name, float(e.start_ns), float(e.end_ns))
                           for e in line.events)
        out[plane.name] = sorted(evs, key=lambda e: e[1])
    return out


def host_events(pd, name: str) -> List[Interval]:
    """(start_ns, end_ns) of every host event called `name`."""
    out = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            out.extend((float(e.start_ns), float(e.end_ns))
                       for e in line.events if e.name == name)
    return sorted(out)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def complement(busy: Sequence[Interval], start: float, end: float
               ) -> List[Interval]:
    gaps, t = [], start
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, end)))
        t = max(t, e)
        if t >= end:
            break
    if t < end:
        gaps.append((t, end))
    return [(s, e) for s, e in gaps if e > s]


def attribute(gaps: Sequence[Interval],
              spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Seconds of idle gap per host span name: each stretch of a gap goes
    to the shortest span that covers it (the innermost of nested spans).
    `gaps` must be sorted and disjoint."""
    out: Dict[str, float] = {}
    spans = sorted(spans, key=lambda s: s[1])
    active: List[Tuple[str, float, float]] = []
    nxt = 0
    for g0, g1 in gaps:
        while nxt < len(spans) and spans[nxt][1] < g1:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[2] > g0]
        cuts = sorted({g0, g1} | {t for _, s, e in active for t in (s, e)
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            cover = [(e - s, name) for name, s, e in active
                     if s <= a and e >= b]
            name = min(cover)[1] if cover else NO_SPAN
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def _clip(evs, start_ns, end_ns):
    return [(nm, max(s, start_ns), min(e, end_ns)) for nm, s, e in evs
            if e > start_ns and s < end_ns]


def reduce(pd, start_ns: float, end_ns: float,
           host_spans: Sequence[Tuple[str, float, float]] = ()
           ) -> Reduction:
    """Reduce the trace between start_ns and end_ns (the trace's clock)."""
    if end_ns <= start_ns:
        raise ValueError("empty trace window")
    per_dev = {k: v for k, v in device_ops(pd).items()
               if any(e > start_ns and s < end_ns for _, s, e in v)}
    n = len(per_dev)
    red = Reduction(window_s=(end_ns - start_ns) * 1e-9, busy_s=0.0,
                    devices=n)
    if not n:
        return red
    programs = device_ops(pd, MODULES_LINE)
    for plane, evs in sorted(per_dev.items()):
        clipped = _clip(evs, start_ns, end_ns)
        busy = union((s, e) for _, s, e in clipped)
        red.busy_s += sum(e - s for s, e in busy) * 1e-9 / n
        for nm, s, e in clipped:
            key = op_name(nm)
            red.ops_s[key] = red.ops_s.get(key, 0.0) + (e - s) * 1e-9 / n
            red.text_s[nm] = red.text_s.get(nm, 0.0) + (e - s) * 1e-9 / n
        for nm, s, e in _clip(programs.get(plane, []), start_ns, end_ns):
            key = program_name(nm)
            red.programs_s[key] = red.programs_s.get(key, 0.0) \
                + (e - s) * 1e-9 / n
        gaps = complement(busy, start_ns, end_ns)
        for name, sec in attribute(gaps, host_spans).items():
            red.idle_by_span_s[name] = red.idle_by_span_s.get(name, 0.0) \
                + sec / n
    return red
