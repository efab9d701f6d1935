"""Device-resident token emission ring + detokenize consumer (DESIGN.md §18).

Token emission used to be the last per-step device->host readback on the
serving hot path: every decode tick fetched `{tok, pos}` so the driver
could append to the per-request streams. But under deferred validation
(DESIGN.md §11) a token only becomes *externally visible truth* at a clean
flush — reading it back earlier buys nothing except a sync. This module
moves emission to the flush cadence:

  * `TokenRing`   -- the device-resident emission ring, the sibling of the
                     engine's commit-predicate ring. Each deferred step
                     PARKS its `(tok, pos)` device refs (no launch, no
                     readback — the refs the jitted step already produced)
                     together with a host-side snapshot of the slot->request
                     owner map. At a flush the ring hands the engine two
                     stacked leaves to FUSE into the same `batched_get` as
                     the combined commit predicate: one transfer batch per
                     `validate_lag` commits carries the predicate AND every
                     token of the window.
  * rollback retraction -- a failed flush localizes `slot_first_bad`; the
                     ring marks the faulty slots' rows at-or-after their
                     first bad step DEAD before anything is delivered, so a
                     slot rollback retracts its un-drained tokens by
                     construction. Clean slots' rows in the same window were
                     examined by the localization read and deliver normally.
  * `DetokenizeConsumer` -- a bounded-queue worker thread (the maxtext
                     decode/detokenize split): the driver submits drained
                     batches and immediately proceeds with the next window's
                     launches; the consumer walks each batch in step order
                     and appends to the request streams. A full queue blocks
                     the driver (backpressure); `quiesce()` drains the queue
                     before any decision that reads request streams
                     (rejection notify, end of run).

Delivered-prefix property: `deliver_batch` appends a token only when its
position extends the stream by exactly one (`target == len(tokens) + 1`),
so frozen slots, re-decoded steps after a rollback and duplicate drains all
collapse to exactly-once delivery per position — and nothing is ever
delivered that a later flush could invalidate, because every delivered row
was validated (or proven clean by the localization read) at its own flush.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import jax.numpy as jnp
import numpy as np

from repro import obs


class _Parked:
    """One decode tick's parked emission: device refs + host bookkeeping."""

    __slots__ = ("step", "tok", "pos", "owners", "dead", "dead_all")

    def __init__(self, step: int, tok, pos, owners: Dict[int, Any]):
        self.step = int(step)
        self.tok = tok                  # (N, 1) device ref
        self.pos = pos                  # (N,)  device ref
        self.owners = owners            # slot -> Request (snapshot at park)
        self.dead: Set[int] = set()     # slots retracted by a failed flush
        self.dead_all = False           # scalar-predicate fallback


@dataclass
class DrainBatch:
    """One drained window, fully on host: what the consumer thread walks."""

    steps: List[int]
    toks: np.ndarray                    # (W, N, 1)
    poss: np.ndarray                    # (W, N)
    owners: List[Dict[int, Any]]        # per-row slot -> Request
    dead: List[Set[int]]                # per-row retracted slots
    dead_all: List[bool]


class TokenRing:
    """Device-resident emission ring, drained at flush boundaries.

    The engine calls `park(step, aux)` inside the deferred step (before its
    own flush check, so a window's last token is never stranded past its
    flush), `provide(final=)` when assembling a flush readback, `truncate`
    on a failed flush and `deliver` with the fetched host arrays. The
    driver owns `owners` (slot -> Request for the slots active this tick)
    and `sink` (usually `DetokenizeConsumer.submit`)."""

    def __init__(self, cadence: int = 1,
                 extract: Optional[Callable[[Any], Tuple[Any, Any]]] = None,
                 sink: Optional[Callable[[DrainBatch], None]] = None,
                 on_token: Optional[Callable[..., None]] = None):
        self.cadence = max(int(cadence), 1)
        self.extract = extract or (lambda aux: (aux[0], aux[1]))
        self.sink = sink
        self.on_token = on_token
        self.owners: Dict[int, Any] = {}
        self._entries: List[_Parked] = []
        self.parked = 0                 # cumulative rows parked
        self.drains = 0                 # drain batches issued
        self.delivered = 0              # tokens appended (inline sink only)
        self.retracted = 0              # tokens retracted (inline sink only)

    def __len__(self) -> int:
        return len(self._entries)

    # -- engine-facing ------------------------------------------------------

    def park(self, step: int, aux) -> None:
        """Park one tick's emission refs. No launch, no readback — the refs
        are the jitted step's own outputs; `owners` is snapshotted so a
        later admission reusing the slot cannot reroute old rows."""
        tok, pos = self.extract(aux)
        self._entries.append(_Parked(step, tok, pos, dict(self.owners)))
        self.parked += 1

    def provide(self, final: bool = False) -> Optional[List[Any]]:
        """Leaves to fuse into the flush readback: `[toks, poss]` stacked
        over the parked window, or None while the drain cadence says keep
        parking (a sub-cadence flush still validates predicates; the rows
        ride along until the cadence fills or the run ends)."""
        if not self._entries:
            return None
        if not final and len(self._entries) < self.cadence:
            return None
        return [jnp.stack([e.tok for e in self._entries]),
                jnp.stack([e.pos for e in self._entries])]

    def truncate(self, slot_first_bad: Optional[Dict[int, int]],
                 global_bad: Optional[int] = None) -> None:
        """Failed-flush retraction: mark faulty slots' rows at-or-after
        their first bad step dead. Applies only to rows parked so far —
        re-decoded rows parked after the rollback are new evidence and
        deliver normally (the position guard de-duplicates)."""
        for e in self._entries:
            if slot_first_bad:
                for slot, fb in slot_first_bad.items():
                    if e.step >= fb:
                        e.dead.add(int(slot))
            elif global_bad is not None and e.step >= global_bad:
                e.dead_all = True

    def deliver(self, vals: List[Any]) -> Optional[DrainBatch]:
        """Hand the fetched window to the sink and reset the ring. `vals`
        must be the host arrays for the leaves `provide()` returned."""
        if not self._entries:
            return None
        toks, poss = np.asarray(vals[0]), np.asarray(vals[1])
        batch = DrainBatch(
            steps=[e.step for e in self._entries],
            toks=toks, poss=poss,
            owners=[e.owners for e in self._entries],
            dead=[e.dead for e in self._entries],
            dead_all=[e.dead_all for e in self._entries])
        self._entries.clear()
        self.drains += 1
        if self.sink is not None:
            self.sink(batch)
        else:
            d, r = deliver_batch(batch, self.on_token)
            self.delivered += d
            self.retracted += r
        return batch

    def clear(self) -> None:
        self._entries.clear()
        self.owners = {}


def deliver_batch(batch: DrainBatch,
                  on_token: Optional[Callable[..., None]] = None,
                  now: Optional[float] = None) -> Tuple[int, int]:
    """Walk one drained window in step order, appending each row's token to
    its owner request when the position extends the stream by exactly one.

    Dead rows (retracted by a failed flush) are counted against the owner's
    `truncated_tokens` when they WOULD have extended the stream — the
    "rolled back + redone" semantics of the per-tick path, tracked through
    a virtual length so a frozen slot's repeated position is not
    over-counted. Returns (delivered, retracted)."""
    stamp = time.time() if now is None else now
    delivered = retracted = 0
    virt: Dict[int, int] = {}           # id(req) -> len(tokens) + retracted
    for i in range(len(batch.steps)):
        owners, dead, dead_all = (batch.owners[i], batch.dead[i],
                                  batch.dead_all[i])
        for slot, req in owners.items():
            target = int(batch.poss[i, slot]) - req.pos0 + 1
            if dead_all or slot in dead:
                v = virt.get(id(req), len(req.tokens))
                if target == v + 1:
                    virt[id(req)] = v + 1
                    req.truncated_tokens += 1
                    retracted += 1
                continue
            if target == len(req.tokens) + 1:
                req.tokens.append(int(batch.toks[i, slot, 0]))
                req.token_times.append(stamp)
                virt[id(req)] = len(req.tokens)
                if on_token is not None:
                    on_token(req, req.tokens[-1], len(req.tokens) - 1)
                delivered += 1
    obs.note_tokens(delivered)
    return delivered, retracted


_STOP = object()


class DetokenizeConsumer:
    """Bounded-queue detokenize thread (maxtext decode/detokenize split).

    The driver `submit()`s drained batches; the worker walks them with
    `deliver_batch` while the driver launches the next window. A full queue
    blocks `submit` (backpressure bounds memory behind a slow client).
    `quiesce()` joins the queue — call it before reading request streams
    (rejection notify, safe-stop, end of run); `close()` shuts the worker
    down after processing everything already queued."""

    def __init__(self, on_token: Optional[Callable[..., None]] = None,
                 max_queue: int = 8):
        self.on_token = on_token
        self._q: "queue.Queue" = queue.Queue(maxsize=max(int(max_queue), 1))
        self._thread: Optional[threading.Thread] = None
        self.delivered = 0
        self.retracted = 0
        self.batches = 0
        self.backlog_peak = 0
        self.errors: List[BaseException] = []

    def start(self) -> "DetokenizeConsumer":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="sedar-detokenize", daemon=True)
            self._thread.start()
        return self

    def submit(self, batch: DrainBatch) -> None:
        if self._thread is None:        # inline fallback (no thread started)
            self._consume(batch)
            return
        self._q.put(batch)              # blocks when full: backpressure
        depth = self._q.qsize()
        if depth > self.backlog_peak:
            self.backlog_peak = depth

    def _consume(self, batch: DrainBatch) -> None:
        with obs.span("detokenize", rows=len(batch.steps)):
            d, r = deliver_batch(batch, self.on_token)
        self.delivered += d
        self.retracted += r
        self.batches += 1

    def _run(self) -> None:
        while True:
            item = self._q.get()
            try:
                if item is _STOP:
                    return
                self._consume(item)
            except BaseException as exc:   # noqa: BLE001 — surfaced at close
                self.errors.append(exc)
            finally:
                self._q.task_done()

    def quiesce(self) -> None:
        """Block until every submitted batch has been fully delivered."""
        if self._thread is not None:
            self._q.join()

    def close(self) -> None:
        """Drain the queue, stop the worker, surface any worker error."""
        if self._thread is not None:
            self._q.put(_STOP)
            self._thread.join()
            self._thread = None
        if self.errors:
            raise self.errors[0]
