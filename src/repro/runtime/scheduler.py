"""Continuous-batching request scheduler for protected serving (DESIGN.md §13).

Three pieces, all host-side and deliberately dumb about the model:

  * `Request`      -- one generation request's full lifecycle record:
                      prompt, budget, arrival tick, emitted tokens with
                      wall-clock stamps, and the slot/recovery bookkeeping
                      the per-request fault story needs (admit step, finish
                      step, truncation count, rejection reason).
  * `RequestQueue` -- bounded FIFO admission queue. `offer()` applies
                      BACKPRESSURE: when the queue is full the request is
                      rejected immediately (load shedding) instead of
                      growing an unbounded backlog behind a fault storm.
  * `SlotScheduler`-- maps requests onto the fixed set of decode slots the
                      packed batch exposes. Slots join/evict mid-flight: a
                      freed slot (finished, rejected) is refilled by the
                      next queued prompt on the SAME decode tick, so the
                      packed protected step always runs over whatever is
                      active — no synchronous wave barrier.

Slot lifecycle:   FREE -> RUNNING -> DRAINING -> FREE
                            ^           |
                            +-- rollback reactivation (deferred fault hit
                                the request's final window)

DRAINING exists because of deferred validation (DESIGN.md §11): a request
that reaches its token budget inside the optimistic window keeps its slot
reserved (decode frozen via the active mask) until the engine's validated
frontier passes its finish step — releasing it earlier could hand the slot
to a new prompt while a pending flush can still prove the old request's
tail corrupt and need the slot's state back for rollback.

The traffic generator (`synthetic_requests`) produces the open-loop replay
workload the launcher and the tests drive: Poisson-ish arrivals at a
configurable rate on the decode-tick clock, a categorical prompt-length
mix, and per-request token budgets — all seeded, so fault campaigns are
bitwise reproducible against their fault-free twins.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.obs import percentile

# Request lifecycle states
PENDING = "pending"      # created, not yet arrived
QUEUED = "queued"        # in the admission queue
RUNNING = "running"      # owns a slot, decoding
DRAINING = "draining"    # token budget reached, awaiting validation
DONE = "done"
REJECTED = "rejected"


@dataclass
class Request:
    """One generation request and its lifecycle record."""

    rid: int
    prompt: np.ndarray                    # (L,) int32 token ids
    max_new_tokens: int
    arrival: int = 0                      # decode tick of arrival (open loop)
    arrival_time: Optional[float] = None  # wall stamp at queue offer (TTFT)
    status: str = PENDING
    slot: Optional[int] = None
    tokens: List[int] = field(default_factory=list)
    token_times: List[float] = field(default_factory=list)  # wall stamps
    pos0: int = 0                         # decode position of the 1st token
    admit_step: Optional[int] = None
    finish_step: Optional[int] = None
    truncated_tokens: int = 0             # rolled back + re-decoded
    reject_reason: str = ""

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def done(self) -> bool:
        return self.status in (DONE, REJECTED)


class RequestQueue:
    """Bounded FIFO with admission control. `max_depth=0` disables the
    bound (accept everything)."""

    def __init__(self, max_depth: int = 0):
        self.max_depth = int(max_depth)
        self._q: deque = deque()
        self.rejected: List[Request] = []

    def __len__(self) -> int:
        return len(self._q)

    def offer(self, req: Request) -> bool:
        """Enqueue, or shed load: a full queue rejects the request NOW
        (status=rejected, reason=backpressure) so callers see bounded
        latency instead of an unbounded backlog."""
        if self.max_depth and len(self._q) >= self.max_depth:
            req.status = REJECTED
            req.reject_reason = "backpressure"
            self.rejected.append(req)
            obs.note_rejection(-1, rid=req.rid, slot=None,
                               reason="backpressure")
            return False
        req.status = QUEUED
        self._q.append(req)
        if obs.metrics_enabled():
            obs.metrics.set_gauge("sedar_serve_queue_depth", len(self._q))
        return True

    def pop(self) -> Optional[Request]:
        req = self._q.popleft() if self._q else None
        if req is not None and obs.metrics_enabled():
            obs.metrics.set_gauge("sedar_serve_queue_depth", len(self._q))
        return req


class SlotScheduler:
    """Slot ownership + lifecycle over the packed decode batch."""

    def __init__(self, n_slots: int, queue: Optional[RequestQueue] = None):
        self.n_slots = int(n_slots)
        # `queue or ...` would discard an EMPTY bounded queue (falsy via
        # __len__) — the same bug class as ClusterMonitor's now=0.0
        self.queue = RequestQueue() if queue is None else queue
        self.slots: List[Optional[Request]] = [None] * self.n_slots

    # -- queries ---------------------------------------------------------------

    def request(self, slot: int) -> Optional[Request]:
        return self.slots[slot]

    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    def items(self, status: str) -> List[Tuple[int, Request]]:
        return [(i, r) for i, r in enumerate(self.slots)
                if r is not None and r.status == status]

    def running_items(self) -> List[Tuple[int, Request]]:
        return self.items(RUNNING)

    def draining_items(self) -> List[Tuple[int, Request]]:
        return self.items(DRAINING)

    @property
    def busy(self) -> bool:
        return any(r is not None for r in self.slots)

    # -- transitions -----------------------------------------------------------

    def admit(self, step: int) -> List[Tuple[int, Request]]:
        """Pair every free slot with the next queued request (FIFO). The
        caller prefills each pair into the packed state."""
        pairs: List[Tuple[int, Request]] = []
        for slot in self.free_slots():
            req = self.queue.pop()
            if req is None:
                break
            req.slot = slot
            req.status = RUNNING
            req.admit_step = step
            self.slots[slot] = req
            pairs.append((slot, req))
        return pairs

    def drain(self, slot: int, finish_step: int) -> None:
        req = self.slots[slot]
        req.status = DRAINING
        req.finish_step = finish_step

    def reactivate(self, slot: int) -> None:
        """Rollback reached into a draining request's final window: it
        resumes decoding its truncated tail."""
        req = self.slots[slot]
        req.status = RUNNING
        req.finish_step = None

    def release(self, slot: int) -> Request:
        req = self.slots[slot]
        req.status = DONE
        req.slot = None
        self.slots[slot] = None
        if obs.metrics_enabled() and \
                req.arrival_time is not None and req.token_times:
            obs.metrics.observe(
                "sedar_serve_ttft_seconds",
                req.token_times[0] - req.arrival_time)
        return req

    def reject(self, slot: int, reason: str) -> Request:
        req = self.slots[slot]
        req.status = REJECTED
        req.reject_reason = reason
        req.slot = None
        self.slots[slot] = None
        return req


# ---------------------------------------------------------------------------
# Open-loop traffic replay
# ---------------------------------------------------------------------------

def synthetic_requests(n: int, *, arrival_rate: float = 1.0,
                       prompt_lengths: Sequence[int] = (4, 8),
                       length_weights: Optional[Sequence[float]] = None,
                       max_new_choices: Sequence[int] = (4, 12),
                       vocab: int = 200, seed: int = 0) -> List[Request]:
    """Seeded open-loop workload: `n` requests with exponential inter-
    arrival gaps at `arrival_rate` requests per decode tick, prompt lengths
    drawn from the categorical mix, and per-request decode budgets from
    `max_new_choices`. Deterministic per seed, so a fault campaign's
    unaffected streams can be compared bitwise against the fault-free run."""
    rs = np.random.RandomState(seed)
    if length_weights is not None:
        w = np.asarray(length_weights, np.float64)
        w = w / w.sum()
    else:
        w = None
    out: List[Request] = []
    t = 0.0
    for rid in range(n):
        if rid:
            t += rs.exponential(1.0 / max(arrival_rate, 1e-9))
        L = int(rs.choice(list(prompt_lengths), p=w))
        out.append(Request(
            rid=rid,
            prompt=rs.randint(0, vocab, (L,)).astype(np.int32),
            max_new_tokens=int(rs.choice(list(max_new_choices))),
            arrival=int(t)))
    return out


def token_latencies(requests: Iterable[Request]) -> List[float]:
    """Per-token INTER-TOKEN gaps across a request set (the streaming
    cadence a client sees); see `ttft_latencies` for time-to-first-token."""
    out: List[float] = []
    for r in requests:
        ts = r.token_times
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def ttft_latencies(requests: Iterable[Request]) -> List[float]:
    """Time-to-first-token per request: first emitted token's wall stamp
    minus the arrival stamp the serve loop cut at queue offer. Requests
    that never emitted (rejected before admission) are excluded — their
    latency is the rejection notice, not a token."""
    out: List[float] = []
    for r in requests:
        if r.arrival_time is not None and r.token_times:
            out.append(r.token_times[0] - r.arrival_time)
    return out


def ttft_percentiles_ms(requests: Iterable[Request]
                        ) -> Tuple[float, float]:
    """(p50, p99) time-to-first-token in milliseconds (0.0, 0.0 when no
    request emitted a first token); nearest-rank via `obs.percentile`."""
    lat = ttft_latencies(requests)
    if not lat:
        return 0.0, 0.0
    return 1e3 * percentile(lat, 50), 1e3 * percentile(lat, 99)


def latency_percentiles_ms(requests: Iterable[Request]
                           ) -> Tuple[float, float]:
    """(p50, p99) inter-token latency in milliseconds (0.0, 0.0 when fewer
    than two tokens were streamed); nearest-rank via `obs.percentile`."""
    lat = token_latencies(requests)
    if not lat:
        return 0.0, 0.0
    return 1e3 * percentile(lat, 50), 1e3 * percentile(lat, 99)


def ttlt_latencies(requests: Iterable[Request]) -> List[float]:
    """Time-to-LAST-token per request (total turnaround a client waits for
    the full stream): last emitted token's wall stamp minus arrival. Under
    lag-aligned drain (DESIGN.md §18) whole windows land at once, so TTLT
    — not the now-bursty inter-token gap — is the end-to-end latency that
    drain cadence actually trades against throughput."""
    out: List[float] = []
    for r in requests:
        if r.arrival_time is not None and r.token_times:
            out.append(r.token_times[-1] - r.arrival_time)
    return out


def ttlt_percentiles_ms(requests: Iterable[Request]
                        ) -> Tuple[float, float]:
    """(p50, p99) time-to-last-token in milliseconds (0.0, 0.0 when no
    request completed a token); nearest-rank via `obs.percentile`."""
    lat = ttlt_latencies(requests)
    if not lat:
        return 0.0, 0.0
    return 1e3 * percentile(lat, 50), 1e3 * percentile(lat, 99)


def stream_stats_ms(requests: Iterable[Request]) -> Dict[str, float]:
    """One bundle of client-visible streaming percentiles in ms: TTFT
    (first token), ITL (inter-token gap) and TTLT (full turnaround) —
    what the `--continuous` CLI summary prints."""
    reqs = list(requests)
    ttft50, ttft99 = ttft_percentiles_ms(reqs)
    itl50, itl99 = latency_percentiles_ms(reqs)
    ttlt50, ttlt99 = ttlt_percentiles_ms(reqs)
    return {"ttft_p50_ms": ttft50, "ttft_p99_ms": ttft99,
            "itl_p50_ms": itl50, "itl_p99_ms": itl99,
            "ttlt_p50_ms": ttlt50, "ttlt_p99_ms": ttlt99}
