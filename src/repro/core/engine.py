"""The unified SEDAR engine: one detection/recovery core for every workload.

Paper Secs. 3.1–3.3 compose three orthogonal mechanisms — replicated
execution (detection), boundary validation (containment), and leveled
checkpointing (recovery). This module is the single place where that
composition lives (DESIGN.md §1):

    SedarEngine = ReplicaExecutor        (how redundant copies execute)
                × BoundarySchedule       (when boundaries fire)
                × recovery policy        (what a detection costs: L0 retry /
                                          L1 stop / L2 chain / L3 validated)
                × Watchdog + injection   (TOE detection, fault campaigns)

Workloads (training, serving, future batch/eval paths) are thin drivers:
they provide a jit-able `step_fn(state, batch, replica_id, armed) ->
(candidate, fingerprint, aux)` plus state fingerprints, then call
`run_protected_step()` per step and `on_detection()` per event. All
compare / commit-gate / validate / checkpoint / rollback / retry logic is
in the engine — no workload re-derives the protocol.

Executor backends:
  * plain       -- no redundancy (the unprotected baseline).
  * sequential  -- time redundancy: both replicas run on the same devices
                   one after the other, each owning a full state image.
  * fused       -- time redundancy in ONE launch (DESIGN.md §11): replica
                   state stacked on a leading axis, both replicas stepped by
                   a single vmapped jit that also computes the equality
                   predicate on device — the zero-sync hot path backend.
  * pod         -- space redundancy: replicas are pods of the production
                   mesh; fingerprints exchanged via all-gather in shard_map.
  * vote        -- N-modular redundancy (beyond-paper, DESIGN.md §6): >=3
                   pod replicas; a divergence is repaired FORWARD by
                   broadcasting the majority replica's state — no rollback.
  * abft/hybrid -- replica-free: checksum-carrying kernels detect (and for
                   single corruptions, forward-correct) in-kernel faults;
                   hybrid adds commit-time fingerprint validation for the
                   classes ABFT cannot see (abft/executor.py, DESIGN.md §10).

Deferred validation (DESIGN.md §11): with `BoundarySchedule.validate_lag=D`
> 1 the engine stops reading the per-step match predicate back to the host.
Executors that `supports_deferred` commit optimistically and hand back the
ON-DEVICE predicate; the engine parks it in a small device-resident ring and
forces one readback every D commits (and at validate/checkpoint/final
boundaries). Detection latency is bounded by D steps; recovery routes
through the unchanged L1/L2/L3 policies, and checkpoints are only cut after
a clean flush, so every stored version predates the oldest unvalidated step.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import hostsync
from repro.core.detection import (DetectionEvent, SedarSafeStop, Watchdog,
                                  majority_replica)
from repro.core.fingerprint import (fingerprints_equal, mismatch_report,
                                    pytree_fingerprint)
from repro.core.recovery import (MultiCheckpointRecovery, RecoveryAction,
                                 RetryRecovery, ValidatedCheckpointRecovery)


# ---------------------------------------------------------------------------
# Boundary schedule
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BoundarySchedule:
    """When each SEDAR boundary fires (cadences in steps; 0 = never).

    commit_interval     -- TDC boundary: replica update-fingerprint compare
                           before the commit (paper: validate-before-send).
    validate_interval   -- FSC boundary: full-state fingerprint compare.
    checkpoint_interval -- L2/L3 checkpoint cadence (t_i analogue).
    toe_timeout_s       -- replica flow-separation lapse (TOE boundary).
    validate_lag        -- deferred validation window D (DESIGN.md §11):
                           commit predicates stay on device and are only
                           read back every D commits. 1 = the classic
                           sync-per-compare behavior; >1 trades detection
                           latency (<= D steps) for a sync-free hot path.
    """

    commit_interval: int = 1
    validate_interval: int = 0
    checkpoint_interval: int = 0
    toe_timeout_s: float = 120.0
    validate_lag: int = 1

    @classmethod
    def from_config(cls, sedar) -> "BoundarySchedule":
        return cls(commit_interval=max(int(sedar.validate_interval), 1),
                   validate_interval=int(sedar.param_validate_interval),
                   checkpoint_interval=int(sedar.checkpoint_interval),
                   toe_timeout_s=float(sedar.toe_timeout_s),
                   validate_lag=max(int(getattr(sedar, "validate_lag", 1)), 1))

    @staticmethod
    def _due(step: int, interval: int) -> bool:
        return interval > 0 and step > 0 and step % interval == 0

    def commit_due(self, step: int) -> bool:
        return self.commit_interval > 0 and step % self.commit_interval == 0

    def validate_due(self, step: int) -> bool:
        return self._due(step, self.validate_interval)

    def checkpoint_due(self, step: int) -> bool:
        return self._due(step, self.checkpoint_interval)


@dataclass
class StepOutcome:
    """Result of one protected step. `dual` is ALWAYS the state to continue
    from: the pre-step state when the commit was gated by a detection, the
    committed state otherwise (recovery then acts on it via on_detection)."""

    dual: Any
    aux: Any = None
    event: Optional[DetectionEvent] = None

    @property
    def committed(self) -> bool:
        return self.event is None or self.event.boundary not in ("commit",
                                                                 "toe")


class _EqCache:
    """One-slot memo for the last state-equality reduction, keyed on the id
    of the committed state object. validate() and validated_fp() land on
    the same state within one engine iteration — the reduction must not run
    twice. Executors invalidate on every execute, so a recycled id can
    never alias a stale entry."""

    __slots__ = ("_key", "_value")

    def __init__(self):
        self._key = None
        self._value = None

    def invalidate(self) -> None:
        self._key = None
        self._value = None

    def get(self, state_obj):
        """Cached value, or None on miss (cached values are never None)."""
        return self._value if self._key == id(state_obj) else None

    def put(self, state_obj, value):
        self._key = id(state_obj)
        self._value = value
        return value


def _default_localizer(c0, c1) -> List[Dict[str, Any]]:
    """Leaf-level localization for a commit mismatch: per-leaf fingerprints
    of the two candidate states (the fused compare fingerprint is a single
    hash — localization recomputes at leaf granularity, off the hot path)."""
    fa, fb = pytree_fingerprint(c0), pytree_fingerprint(c1)
    return mismatch_report(c0, fa, fb)[:4]


# ---------------------------------------------------------------------------
# Replica executors
# ---------------------------------------------------------------------------

class ReplicaExecutor:
    """Protocol for redundant-execution backends.

    execute(dual, batch, step, armed, compare)
        -> (dual', aux, event | None); dual' == dual (by value) when event
           is not None.
    execute_deferred(dual, batch, step, armed, compare)
        -> (dual', aux, pred) where `pred` is the ON-DEVICE bool predicate
           "this step's replicas matched" and the commit is OPTIMISTIC
           (candidates adopted without reading pred — the engine's deferred
           ring decides when to sync). Only when `supports_deferred`.
    validate(dual, step)      -> DetectionEvent | None  (FSC boundary)
    validated_fp(dual)        -> (per-leaf fp of r0 [np], replicas_equal)
    init_dual(single)         -> dual state from one logical state
    adopt_single(single)      -> dual state from a restored L3 checkpoint
    primary(dual)             -> replica 0's logical state (the view drivers
                                 read tokens/steps from and L3 checkpoints)
    state_fp(dual)            -> per-leaf fingerprint of r0 (reporting)
    repair(event, dual)       -> (dual', record) | None  (forward correction)
    """

    name = "base"
    n_replicas = 1
    supports_deferred = False

    @property
    def can_validate(self) -> bool:
        """Whether the ENGINE should drive the periodic FSC boundary by
        calling `validate()` after commits (replica backends: compare
        replicas). Executors that implement their own periodic check (abft
        hybrid validates at step ENTRY) return False here and
        `can_validate_final` True."""
        return self.n_replicas > 1

    @property
    def can_validate_final(self) -> bool:
        """Whether `validate()` is meaningful for the end-of-run final
        comparison (paper Sec. 3.1)."""
        return self.can_validate

    def init_dual(self, single):
        return {"r0": single}

    def adopt_single(self, single):
        return {"r0": single}

    def primary(self, dual):
        return dual["r0"]

    def peek(self, dual, key: str):
        """Replica-0 view of ONE top-level state entry — what drivers read
        tokens/step counters through (cheaper than `primary()`, which slices
        every leaf)."""
        return dual["r0"][key]

    def slot_images(self, dual, axes: Dict[str, int]) -> Tuple[Any, ...]:
        """Replica 0's entries `axes` names, split along each one's slot
        axis into one image per slot, in ONE launch (`sedar_slot_snapshot`):
        the serving loop's Tier-0 snapshot source (DESIGN.md §13). Every
        image is a fresh buffer that no donated state aliases."""
        return sedar_slot_snapshot({k: dual["r0"][k] for k in axes},
                                   tuple(axes.items()), False)

    def map_state(self, fn, dual, *others):
        """Apply `fn` to EVERY replica's logical state (driver-side state
        surgery: slot admission / eviction / per-slot rollback merges in the
        serving path, DESIGN.md §13). `others` are additional duals whose
        matching replica states are passed as extra positional args. The
        transformation must be replica-symmetric — applying anything
        divergent would manufacture a detection."""
        return {"r0": fn(dual["r0"], *[o["r0"] for o in others])}

    def note_external_update(self) -> None:
        """Drivers call this after `map_state` mutated the resident state
        outside a protected step, so executors that cache state-derived
        baselines (e.g. the hybrid commit-time fingerprint) can drop them
        instead of flagging the legitimate mutation as corruption."""

    def execute_deferred(self, dual, batch, step: int, armed,
                         compare: bool = True):
        raise NotImplementedError(
            f"backend {self.name!r} does not support deferred validation")

    def repair(self, event: DetectionEvent, dual
               ) -> Optional[Tuple[Any, Dict[str, Any]]]:
        return None

    def validate(self, dual, step: int) -> Optional[DetectionEvent]:
        return None

    def validated_fp(self, dual) -> Tuple[np.ndarray, bool]:
        return np.asarray(self.state_fp(dual)), True

    def state_fp(self, dual):
        raise NotImplementedError


class PlainExecutor(ReplicaExecutor):
    """No redundancy: the unprotected baseline (replication='none')."""

    name = "none"
    n_replicas = 1

    def __init__(self, step_fn: Callable, state_fp_fn: Callable):
        self.step_fn = step_fn
        self.state_fp_fn = state_fp_fn

    def execute(self, dual, batch, step: int, armed, compare: bool):
        cand, _fp, aux = self.step_fn(dual["r0"], batch, jnp.asarray(0),
                                      armed)
        return {"r0": cand}, aux, None

    def state_fp(self, dual):
        return self.state_fp_fn(dual["r0"])


class SequentialExecutor(ReplicaExecutor):
    """Time redundancy: replicas run back-to-back on the same devices, each
    owning a FULL state image (the paper's per-thread memory image), so
    FSC-class corruption is representable and detectable."""

    name = "sequential"
    n_replicas = 2
    supports_deferred = True

    def __init__(self, step_fn: Callable, state_fp_fn: Callable,
                 fast_state_fp_fn: Optional[Callable] = None,
                 watchdog: Optional[Watchdog] = None,
                 toe_timeout_s: float = 120.0,
                 delay_source: Optional[Callable[[], dict]] = None,
                 localizer: Callable = _default_localizer):
        self.step_fn = step_fn
        self.state_fp_fn = state_fp_fn
        self.fast_state_fp_fn = fast_state_fp_fn or state_fp_fn
        self.watchdog = watchdog
        self.toe_timeout_s = toe_timeout_s
        self.delay_source = delay_source or (lambda: {})
        self.localizer = localizer
        # EMA of the UNSYNCED per-step dispatch wall (jit-level cost): the
        # fast path never calls block_until_ready just to measure time
        self.ema_step_s: Optional[float] = None
        self._val_cache = _EqCache()

    def init_dual(self, single):
        return {"r0": single, "r1": jax.tree.map(jnp.copy, single)}

    adopt_single = init_dual   # a validated single state seeds both replicas

    def _timing_armed(self, delays: dict) -> bool:
        """Per-replica wall-clock separation (the TOE lapse) requires a
        device sync after EACH replica; pay it only when the boundary can
        actually fire — a scenario delay is pending or the watchdog was
        armed explicitly. Otherwise replica launches overlap freely."""
        return bool(delays) or (self.watchdog is not None
                                and getattr(self.watchdog, "armed", False))

    def _launch(self, dual, batch, step: int, armed, timed: bool,
                delays: dict):
        outs, exec_t = {}, {}
        for rid in range(self.n_replicas):
            # one-shot scenario hook (the paper injects the delay once; the
            # re-execution after recovery is not delayed again)
            delay = delays.pop((step, rid), None)
            t_r = time.monotonic()
            if delay:
                time.sleep(delay)
            outs[rid] = self.step_fn(dual[f"r{rid}"], batch,
                                     jnp.asarray(rid), armed)
            if timed:
                jax.block_until_ready(outs[rid][1])
            exec_t[rid] = time.monotonic() - t_r
            if self.watchdog is not None:
                self.watchdog.beat(rid, step)
        self._val_cache.invalidate()
        return outs, exec_t

    def _note_wall(self, t0: float) -> None:
        dt = time.monotonic() - t0
        self.ema_step_s = dt if self.ema_step_s is None else \
            0.9 * self.ema_step_s + 0.1 * dt

    def _launch_with_toe(self, dual, batch, step: int, armed):
        """Timed dual launch + TOE boundary, shared by the plain and
        slotted sequential executors. Returns (outs, toe_event | None);
        TOE only fires when the per-replica walls were actually synced."""
        delays = self.delay_source() or {}
        timed = self._timing_armed(delays)
        t0 = time.monotonic()
        outs, exec_t = self._launch(dual, batch, step, armed, timed, delays)
        self._note_wall(t0)
        if timed and abs(exec_t[1] - exec_t[0]) > self.toe_timeout_s:
            return outs, DetectionEvent(
                step=step, boundary="toe", effect="TOE",
                detail={"dt0": exec_t[0], "dt1": exec_t[1],
                        "timeout_s": self.toe_timeout_s})
        return outs, None

    def execute(self, dual, batch, step: int, armed, compare: bool):
        outs, toe = self._launch_with_toe(dual, batch, step, armed)
        if toe is not None:
            return dual, outs[0][2], toe

        (c0, fp0, aux0), (c1, fp1, _aux1) = outs[0], outs[1]
        if compare and not hostsync.read_bool(fingerprints_equal(fp0, fp1),
                                              label="commit_compare"):
            detail = {"mismatch": self.localizer(c0, c1)}
            return dual, aux0, DetectionEvent(step=step, boundary="commit",
                                              effect="TDC", detail=detail)
        # containment held (or compare skipped this step): adopt candidates
        return {"r0": c0, "r1": c1}, aux0, None

    def execute_deferred(self, dual, batch, step: int, armed,
                         compare: bool = True):
        """Optimistic commit: both candidates adopted, the match predicate
        stays on device for the engine's deferred ring. No TOE timing (it
        would reintroduce the per-replica sync this path exists to avoid)."""
        delays = self.delay_source() or {}
        t0 = time.monotonic()
        outs, _ = self._launch(dual, batch, step, armed, False, delays)
        self._note_wall(t0)
        (c0, fp0, aux0), (c1, fp1, _aux1) = outs[0], outs[1]
        pred = fingerprints_equal(fp0, fp1)
        return {"r0": c0, "r1": c1}, aux0, pred

    def _resident_eq(self, dual) -> bool:
        """Full-state replica comparison, cached per dual object (_EqCache):
        re-reducing it between validate() and validated_fp() would double
        the FSC cost."""
        hit = self._val_cache.get(dual.get("r0"))
        if hit is not None:
            return hit
        fp0 = self.fast_state_fp_fn(dual["r0"])
        fp1 = self.fast_state_fp_fn(dual["r1"])
        equal = hostsync.read_bool(fingerprints_equal(fp0, fp1),
                                   label="state_validate")
        return self._val_cache.put(dual.get("r0"), equal)

    def validate(self, dual, step: int) -> Optional[DetectionEvent]:
        if self._resident_eq(dual):
            return None
        return DetectionEvent(step=step, boundary="validate", effect="FSC")

    def validated_fp(self, dual) -> Tuple[np.ndarray, bool]:
        return (hostsync.read_scalar(self.state_fp_fn(dual["r0"]),
                                     label="validated_fp"),
                self._resident_eq(dual))

    def state_fp(self, dual):
        return self.state_fp_fn(dual["r0"])

    def map_state(self, fn, dual, *others):
        return {"r0": fn(dual["r0"], *[o["r0"] for o in others]),
                "r1": fn(dual["r1"], *[o["r1"] for o in others])}


# ---------------------------------------------------------------------------
# Slot-granular executors (continuous-batching serving, DESIGN.md §13)
# ---------------------------------------------------------------------------

def _slot_eq(fp0, fp1) -> jnp.ndarray:
    """Per-slot replica equality from PER-SLOT fingerprints (N, 4): exact
    match on the hash words, one bool per sequence slot."""
    return jnp.all(fp0[..., :2] == fp1[..., :2], axis=-1)


def _slot_mismatch_event(eq, step: int,
                         extra: Optional[Dict[str, Any]] = None
                         ) -> DetectionEvent:
    """Fault-path localization shared by the slotted backends: ONE extra
    readback resolves the per-slot equality vector into the event's slot
    list (`detail={slots, partial, ...}`)."""
    eq_h = hostsync.read_scalar(eq, label="slot_compare")
    bad = [int(i) for i in np.nonzero(~np.asarray(eq_h, bool))[0]]
    detail: Dict[str, Any] = {"slots": bad, "partial": True}
    if extra:
        detail.update(extra)
    return DetectionEvent(step=step, boundary="commit", effect="TDC",
                          detail=detail)


def slot_image(x, i, axis: int):
    """Slot `i`'s image of a packed-state leaf whose slot axis is `axis`. A
    leading slot axis stacks whole per-slot entries and is dropped; a later
    one is a batch axis inside the entry (a layer-major cache's (L, slots,
    T, ...)) and is kept with size 1, so a cache image is (L, 1, T, ...) in
    either layout: the layout of a one-row prefill's cache."""
    return jax.lax.index_in_dim(x, i, axis, keepdims=axis > 0)


@functools.partial(jax.jit, static_argnums=(1, 2))
def sedar_slot_snapshot(entries, axes: Tuple[Tuple[str, int], ...],
                        stacked: bool):
    """Split `entries` into one image per slot (`slot_image`; `axes`: the
    slot axis of each entry, as (key, axis) pairs). Under a stacked layout
    (replica axis first) replica 0 is selected inside the program, so XLA
    fuses the replica index into each slot's slice and no whole-replica
    image is materialized. Every slot is extracted, so the program has one
    shape for any number of running slots."""
    axes = dict(axes)
    if stacked:
        entries = jax.tree.map(lambda x: x[0], entries)
    k0 = next(iter(entries))
    n = jax.tree.leaves(entries[k0])[0].shape[axes[k0]]
    return tuple({k: jax.tree.map(
        lambda x, i=i, a=axes[k]: slot_image(x, i, a), v)
        for k, v in entries.items()} for i in range(n))


def slot_select(mask, new, old, axes: Dict[str, int]):
    """Per-slot merge of two packed states: `where(mask)` along the slot
    axis of each entry, which `axes` gives by top-level key (it differs
    between entries: a layer-major cache carries the slot on axis 1, the
    tokens and positions on axis 0). Entries without a slot axis (e.g. the
    global decode tick) adopt `new` unconditionally."""
    def sel(axis, a, b):
        m = jnp.reshape(mask, (1,) * axis + mask.shape
                        + (1,) * (a.ndim - axis - 1))
        return jnp.where(m, a, b)
    return {k: (jax.tree.map(functools.partial(sel, axes[k]), v, old[k])
                if k in axes else v) for k, v in new.items()}


class SlottedSequentialExecutor(SequentialExecutor):
    """Time redundancy over a PACKED sequence batch (DESIGN.md §13): the
    step_fn's fingerprint carries a leading slot axis (N, 4), so a commit
    mismatch is LOCALIZED to sequence slots and the matching slots'
    candidates are PARTIALLY COMMITTED — one corrupted sequence no longer
    gates the whole batch. Faulty slots keep their pre-step image (their
    per-slot position does not advance), so the next protected step simply
    re-decodes them while the committed slots stream on: the rework quantum
    is the affected sequence, not the batch (cf. Samfass & Weinzierl,
    task-local redundancy)."""

    name = "slotted"

    def __init__(self, *args, slot_axes: Dict[str, int], **kw):
        super().__init__(*args, **kw)
        self.slot_axes = dict(slot_axes)

    def execute(self, dual, batch, step: int, armed, compare: bool):
        outs, toe = self._launch_with_toe(dual, batch, step, armed)
        if toe is not None:
            return dual, outs[0][2], toe
        (c0, fp0, aux0), (c1, fp1, _aux1) = outs[0], outs[1]
        if not compare:
            return {"r0": c0, "r1": c1}, aux0, None
        eq = _slot_eq(fp0, fp1)
        if hostsync.read_bool(jnp.all(eq), label="commit_compare"):
            return {"r0": c0, "r1": c1}, aux0, None
        # fault path: the matching slots commit and only the faulty ones
        # stay pre-step
        merged = {"r0": slot_select(eq, c0, dual["r0"], self.slot_axes),
                  "r1": slot_select(eq, c1, dual["r1"], self.slot_axes)}
        return merged, aux0, _slot_mismatch_event(eq, step)

    def execute_deferred(self, dual, batch, step: int, armed,
                         compare: bool = True):
        """Optimistic per-slot commit: the (N,) match-predicate VECTOR joins
        the engine's deferred ring, so a failed flush localizes both the
        step and the slots."""
        delays = self.delay_source() or {}
        t0 = time.monotonic()
        outs, _ = self._launch(dual, batch, step, armed, False, delays)
        self._note_wall(t0)
        (c0, fp0, aux0), (c1, fp1, _aux1) = outs[0], outs[1]
        return {"r0": c0, "r1": c1}, aux0, _slot_eq(fp0, fp1)


class FusedSequentialExecutor(ReplicaExecutor):
    """Time redundancy in ONE launch (DESIGN.md §11): replica state is
    stacked on a leading axis and both replicas are stepped by a single
    vmapped jit that also computes the replica-equality predicate on device.

    Versus `SequentialExecutor` this removes, per protected step: one kernel
    dispatch (two launches fuse into one), two `block_until_ready` syncs and
    — with the in-jit commit gate or the deferred ring — the per-step host
    readback of the compare bit. With buffer donation the stacked state is
    updated in place, so the dual image stops doubling peak memory on copy.

    The commit gate mirrors the pod backend: candidates are committed only
    `where(eq)`, so a mismatch returns the pre-step values and L0 retry
    re-executes from them even though the input buffers were donated.
    Deferred mode runs the SAME compiled program (one executable for both
    lag modes keeps trajectories bitwise-identical across `validate_lag`
    settings — a second lowering would reassociate float ops) and merely
    skips the predicate readback: a deferred mismatch freezes the replicas
    in place, later steps run batch-skewed until the ring flush localizes
    the faulty step, and checkpoint rollback repairs the skew. Per-replica
    TOE timing is not representable — the replicas share one launch; the
    TOE boundary needs the sequential backend."""

    name = "fused"
    n_replicas = 2
    supports_deferred = True

    def __init__(self, step_fn: Callable, state_fp_fn: Callable,
                 fast_state_fp_fn: Optional[Callable] = None,
                 watchdog: Optional[Watchdog] = None, donate: bool = True):
        self.step_fn = step_fn
        self.state_fp_fn = state_fp_fn
        self.fast_state_fp_fn = fast_state_fp_fn or state_fp_fn
        self.watchdog = watchdog
        self._val_cache = _EqCache()
        self._build_programs(step_fn, donate)

    # -- overridable reduction/commit hooks (the slotted subclass swaps
    # ONLY these two; the launch/validate/donation machinery is shared) ----

    def _replica_eq(self, fps):
        """Traced replica-equality reduction over the stacked fps."""
        return fingerprints_equal(fps[0], fps[1])

    def _commit_gate(self, commit, cands, stacked):
        """Traced commit: adopt `cands` where `commit` holds, else keep
        `stacked` (pre-step). Scalar-predicate gate as a lax.cond, NOT a
        per-leaf jnp.where: select lowers to a full elementwise pass over
        both operands of every leaf (~3x the whole step on CPU), while the
        conditional just forwards the chosen pytree."""
        return jax.lax.cond(jnp.all(commit), lambda c, s: c,
                            lambda c, s: s, cands, stacked)

    def _build_programs(self, step_fn: Callable, donate: bool) -> None:
        n = self.n_replicas

        def _core(stacked, batch, armed):
            rids = jnp.arange(n, dtype=jnp.int32)
            cands, fps, auxs = jax.vmap(
                step_fn, in_axes=(0, None, 0, None))(stacked, batch, rids,
                                                     armed)
            return cands, self._replica_eq(fps), \
                jax.tree.map(lambda a: a[0], auxs)

        def _gated(stacked, batch, armed, compare):
            cands, eq, aux0 = _core(stacked, batch, armed)
            # the gate only bites on compare steps: off-boundary steps must
            # adopt the candidates unconditionally (like the sequential
            # backend) or a divergence there would be silently REVERTED and
            # never reach a detection boundary
            commit = jnp.logical_or(eq, jnp.logical_not(compare))
            return self._commit_gate(commit, cands, stacked), eq, aux0

        def _validate(stacked):
            fps = jax.vmap(self.fast_state_fp_fn)(stacked)
            return fingerprints_equal(fps[0], fps[1])

        # fixed program names (`jit_sedar_fused_decode_step` when serving):
        # the profiler's `XLA Modules` line names each launch by them
        step = getattr(step_fn, "__name__", "step").removeprefix("sedar_")
        _gated.__name__ = _gated.__qualname__ = f"sedar_fused_{step}"
        _validate.__name__ = _validate.__qualname__ = "sedar_fused_validate"
        # the same donation on every backend: the CPU tests run the aliasing
        # the chip runs, so a stale reference to a donated buffer fails there
        self._step_gated = jax.jit(_gated,
                                   donate_argnums=(0,) if donate else ())
        self._validate_jit = jax.jit(_validate)

    def init_dual(self, single):
        return {"s": jax.tree.map(
            lambda x: jnp.stack([jnp.asarray(x)] * self.n_replicas), single)}

    adopt_single = init_dual

    def primary(self, dual):
        return jax.tree.map(lambda x: x[0], dual["s"])

    def peek(self, dual, key: str):
        return jax.tree.map(lambda x: x[0], dual["s"][key])

    def slot_images(self, dual, axes: Dict[str, int]) -> Tuple[Any, ...]:
        return sedar_slot_snapshot({k: dual["s"][k] for k in axes},
                                   tuple(axes.items()), True)

    def _beat(self, step: int) -> None:
        if self.watchdog is not None:
            for rid in range(self.n_replicas):
                self.watchdog.beat(rid, step)

    def _launch(self, dual, batch, step: int, armed, compare: bool):
        new, eq, aux = self._step_gated(dual["s"], batch, armed,
                                        jnp.asarray(compare, jnp.bool_))
        self._val_cache.invalidate()
        self._beat(step)
        return {"s": new}, eq, aux

    def execute(self, dual, batch, step: int, armed, compare: bool):
        dual2, eq, aux = self._launch(dual, batch, step, armed, compare)
        if compare and not hostsync.read_bool(eq, label="commit_compare"):
            # gated: dual2 carries the pre-step values (leaf-level
            # localization would need the discarded candidates; the fused
            # hot path trades it away — the sequential backend keeps it)
            return dual2, aux, DetectionEvent(step=step, boundary="commit",
                                              effect="TDC",
                                              detail={"fused": True})
        return dual2, aux, None

    def execute_deferred(self, dual, batch, step: int, armed,
                         compare: bool = True):
        dual2, eq, aux = self._launch(dual, batch, step, armed, compare)
        return dual2, aux, eq

    def _resident_eq(self, dual) -> bool:
        hit = self._val_cache.get(dual.get("s"))
        if hit is not None:
            return hit
        equal = hostsync.read_bool(self._validate_jit(dual["s"]),
                                   label="state_validate")
        return self._val_cache.put(dual.get("s"), equal)

    def validate(self, dual, step: int) -> Optional[DetectionEvent]:
        if self._resident_eq(dual):
            return None
        return DetectionEvent(step=step, boundary="validate", effect="FSC")

    def validated_fp(self, dual) -> Tuple[np.ndarray, bool]:
        return (hostsync.read_scalar(self.state_fp_fn(self.primary(dual)),
                                     label="validated_fp"),
                self._resident_eq(dual))

    def state_fp(self, dual):
        return self.state_fp_fn(self.primary(dual))

    def map_state(self, fn, dual, *others):
        """Unstack -> apply per replica -> restack. Driver-side surgery is
        off the hot path, so the extra copies are acceptable; fn must be
        replica-symmetric (see the base-class contract)."""
        outs = []
        for i in range(self.n_replicas):
            args = [jax.tree.map(lambda x, i=i: x[i], d["s"])
                    for d in (dual,) + tuple(others)]
            outs.append(fn(*args))
        return {"s": jax.tree.map(lambda *xs: jnp.stack(list(xs)), *outs)}


class SlottedFusedExecutor(FusedSequentialExecutor):
    """Single-launch time redundancy over a packed sequence batch
    (DESIGN.md §13): per-slot fingerprints, and the in-jit commit gate is
    PER SLOT — a `lax.cond` keeps the fault-free path free of the per-leaf
    select (all slots matched -> forward the candidate pytree), and only a
    mismatching step pays the slot-masked merge. Deferred mode runs the
    SAME compiled program and parks the (N,) predicate in the engine ring."""

    name = "slotted_fused"

    def __init__(self, step_fn: Callable, state_fp_fn: Callable,
                 fast_state_fp_fn: Optional[Callable] = None,
                 watchdog: Optional[Watchdog] = None, donate: bool = True,
                 *, slot_axes: Dict[str, int]):
        # before _build_programs traces; the replica stack leads each leaf
        self.slot_axes = {k: a + 1 for k, a in slot_axes.items()}
        super().__init__(step_fn, state_fp_fn,
                         fast_state_fp_fn=fast_state_fp_fn,
                         watchdog=watchdog, donate=donate)

    def _replica_eq(self, fps):
        return _slot_eq(fps[0], fps[1])              # (n_slots,)

    def _commit_gate(self, commit, cands, stacked):
        # per-slot gate along each entry's slot axis, one further in under
        # the replica stack. lax.cond keeps the all-matched fault-free path
        # free of the per-leaf slot_select pass
        return jax.lax.cond(
            jnp.all(commit), lambda c, s: c,
            lambda c, s: slot_select(commit, c, s, self.slot_axes),
            cands, stacked)

    def execute(self, dual, batch, step: int, armed, compare: bool):
        dual2, eq, aux = self._launch(dual, batch, step, armed, compare)
        if compare and not hostsync.read_bool(jnp.all(eq),
                                              label="commit_compare"):
            # dual2 already carries the per-slot partial commit (in-jit)
            return dual2, aux, _slot_mismatch_event(eq, step,
                                                    {"fused": True})
        return dual2, aux, None

    def execute_deferred(self, dual, batch, step: int, armed,
                         compare: bool = True):
        dual2, eq, aux = self._launch(dual, batch, step, armed, compare)
        return dual2, aux, eq


class PodExecutor(ReplicaExecutor):
    """Space redundancy: replicas are pods of the production mesh; one jit'd
    step runs the compare + gated commit inside shard_map.

    `pod_step(state, batch, armed) -> (new_state, eq, fp_all, aux)` must
    commit candidates only where eq (the in-jit analogue of the sequential
    compare-then-commit); `pod_validate(state) -> (eq, fp_all)` compares
    full-state fingerprints over the replica axis.

    `eq` may be a scalar (legacy whole-state compare) or a per-lane bool
    vector from `make_lane_comparator` (DESIGN.md §16) — all hot-path reads
    reduce it with jnp.all; the lane vector itself is only read back on the
    fault path, where `lane_hosts` (lane indices -> host ids) translates it
    into a device/host localization on the DetectionEvent."""

    name = "pod"
    n_replicas = 2
    supports_deferred = True

    def __init__(self, pod_step: Callable, pod_validate: Callable,
                 state_fp_fn: Callable, *,
                 lane_hosts: Optional[Callable] = None):
        self.pod_step = pod_step
        self.pod_validate = pod_validate
        self.state_fp_fn = state_fp_fn
        self.lane_hosts = lane_hosts
        # last pod_validate reduction (_EqCache): validate() and
        # validated_fp() hit the same committed state in one engine
        # iteration — the all-gather compare must not run twice
        self._val_cache = _EqCache()

    def _lane_detail(self, eq) -> Dict[str, Any]:
        """Fault-path-only localization: read the per-lane predicate back
        and name the disagreeing lanes (and their owning hosts)."""
        if jnp.ndim(eq) == 0:
            return {}
        vec = np.asarray(hostsync.batched_get([eq],
                                              label="commit_lanes")[0])
        lanes = [int(i) for i in np.nonzero(~vec)[0]]
        detail: Dict[str, Any] = {"lanes": lanes}
        if self.lane_hosts is not None and lanes:
            detail["hosts"] = sorted({int(h)
                                      for h in self.lane_hosts(lanes)})
        return detail

    def annotate_event(self, event: DetectionEvent) -> None:
        """Deferred-flush events localize per ring slot; for the pod
        backend a ring slot IS a fingerprint lane — translate."""
        slots = event.detail.get("slots")
        if slots and "lanes" not in event.detail:
            event.detail["lanes"] = list(slots)
            if self.lane_hosts is not None:
                event.detail["hosts"] = sorted(
                    {int(h) for h in self.lane_hosts(slots)})

    def execute(self, dual, batch, step: int, armed, compare: bool):
        new_state, eq, fp_all, aux = self.pod_step(dual["r0"], batch, armed)
        self._val_cache.invalidate()
        if compare and not hostsync.read_bool(jnp.all(eq),
                                              label="commit_compare"):
            return dual, aux, DetectionEvent(step=step, boundary="commit",
                                             effect="TDC",
                                             detail=self._lane_detail(eq))
        return {"r0": new_state}, aux, None

    def execute_deferred(self, dual, batch, step: int, armed,
                         compare: bool = True):
        """pod_step gates the commit in-jit, so a deferred mismatch FREEZES
        the state rather than diverging it; the ring flush still localizes
        the faulty step and rollback repairs the (batch-skewed) replay."""
        new_state, eq, fp_all, aux = self.pod_step(dual["r0"], batch, armed)
        self._val_cache.invalidate()
        return {"r0": new_state}, aux, eq

    def _state_eq(self, dual):
        hit = self._val_cache.get(dual.get("r0"))
        if hit is not None:
            return hit
        eq, fp_all = self.pod_validate(dual["r0"])
        eqb = hostsync.read_bool(jnp.all(eq), label="state_validate")
        return self._val_cache.put(dual.get("r0"), (eqb, fp_all, eq))

    def validate(self, dual, step: int) -> Optional[DetectionEvent]:
        eqb, fp_all, eq = self._state_eq(dual)
        if eqb:
            return None
        detail = {"fp_all": hostsync.read_scalar(fp_all, label="fp_all")}
        detail.update(self._lane_detail(eq))
        return DetectionEvent(step=step, boundary="validate", effect="FSC",
                              detail=detail)

    def validated_fp(self, dual) -> Tuple[np.ndarray, bool]:
        eqb = self._state_eq(dual)[0]
        return (hostsync.read_scalar(self.state_fp_fn(dual["r0"]),
                                     label="validated_fp"), eqb)

    def state_fp(self, dual):
        return self.state_fp_fn(dual["r0"])


class VoteExecutor(PodExecutor):
    """Beyond-paper N-modular redundancy (DESIGN.md §6): >=3 pod replicas.

    A state divergence is repaired FORWARD by broadcasting the majority
    replica's state (no rollback, no recomputation); a transient commit
    mismatch simply re-executes. Falls back to the engine's recovery policy
    when no strict majority exists. Deferred validation is disabled: the
    forward-repair protocol consumes the per-step predicate (and fp_all)
    immediately."""

    name = "vote"
    supports_deferred = False

    def __init__(self, pod_step: Callable, pod_validate: Callable,
                 state_fp_fn: Callable, broadcaster: Callable,
                 n_replicas: int = 3):
        super().__init__(pod_step, pod_validate, state_fp_fn)
        self.broadcaster = broadcaster
        self.n_replicas = n_replicas

    def repair(self, event: DetectionEvent, dual
               ) -> Optional[Tuple[Any, Dict[str, Any]]]:
        if event.boundary in ("validate", "final") and \
                "fp_all" in event.detail:
            src, ok = majority_replica(event.detail["fp_all"])
            if ok:
                repaired = self.broadcaster(src)(dual["r0"])
                return {"r0": repaired}, {"kind": "vote_repair", "step": None,
                                          "rollbacks": 0, "src_replica": src}
            return None
        if event.boundary == "commit":
            # transient update fault: simple re-execution, no rollback
            return dual, {"kind": "vote_retry", "step": None, "rollbacks": 0}
        return None


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

def _deliver(emis, vals) -> None:
    """Hand a flushed token window, already on the host, to the emission
    ring's sink (the `token_deliver` span: the host work after the flush's
    readback, while the device waits for the next step)."""
    with obs.span("token_deliver", rows=len(emis)):
        emis.deliver(vals)


class SedarEngine:
    """Composes executor × schedule × recovery × watchdog × injection behind
    `run_protected_step()` + `on_detection()` (DESIGN.md §1).

    The engine owns the event/recovery/checkpoint records for a run
    (`detections`, `recoveries`, `checkpoints`); drivers alias or copy them
    into their own reports. Call `reset()` at the start of each run."""

    def __init__(self, executor: ReplicaExecutor, schedule: BoundarySchedule,
                 recovery, *, watchdog: Optional[Watchdog] = None,
                 inj_spec=None, inj_flag=None,
                 init_fn: Optional[Callable[[], Any]] = None,
                 notify: Optional[Callable[[DetectionEvent], None]] = None):
        self.executor = executor
        self.schedule = schedule
        self.recovery = recovery
        self.watchdog = watchdog
        self.inj_spec = inj_spec
        self.inj_flag = inj_flag
        self.init_fn = init_fn
        self.notify = notify or (lambda e: print(str(e), flush=True))
        self.detections: List[DetectionEvent] = []
        self.recoveries: List[Dict[str, Any]] = []
        self.checkpoints: List[int] = []
        # -- deferred validation window (DESIGN.md §11) ---------------------
        # The effective lag degrades to 1 (classic sync-per-compare) when the
        # executor cannot hand back an on-device predicate, or when recovery
        # is L0 re-execution: a retry can only rewind the CURRENT step, and
        # with optimistic commits the faulty step is up to D steps in the
        # past — only checkpoint rollback (or a stop) can reach it.
        lag = max(int(getattr(schedule, "validate_lag", 1)), 1)
        if lag > 1 and not getattr(executor, "supports_deferred", False):
            lag = 1
        if lag > 1 and isinstance(recovery, RetryRecovery):
            lag = 1
        self.validate_lag = lag
        self._ring: List[Tuple[int, Any]] = []   # device-resident predicates
        self.validated_frontier = 0              # first step NOT yet validated
        # device-resident token emission ring (DESIGN.md §18): when a
        # serving driver attaches one, every deferred step parks its
        # emission refs and flush_deferred fuses the drained window into
        # the SAME readback as the combined commit predicate
        self.emission_ring = None
        self.extra_values: List[Any] = []        # flush_deferred(extra=...)
        # -- live reconfiguration (DESIGN.md §17) ---------------------------
        # autotuner transitions are per-run: reset() restores the configured
        # baseline so a cached engine (serve's _batch_engines) never leaks a
        # tuned knob into the next run
        self.reconfigs: List[Dict[str, Any]] = []
        self._base_schedule = self.schedule
        self._base_lag = self.validate_lag

    @property
    def pending_validation(self) -> bool:
        """True while deferred predicates are parked in the device ring."""
        return bool(self._ring)

    # -- lifecycle ----------------------------------------------------------

    def reset(self) -> None:
        self.detections.clear()
        self.recoveries.clear()
        self.checkpoints.clear()
        self._ring.clear()
        self.validated_frontier = 0
        self.emission_ring = None     # drivers re-attach per run
        self.extra_values = []
        self.reconfigs.clear()
        self.schedule = self._base_schedule
        self.validate_lag = self._base_lag

    def apply_reconfig(self, *, validate_lag: Optional[int] = None,
                       checkpoint_interval: Optional[int] = None,
                       tier_schedule=None,
                       reason: str = "") -> Optional[Dict[str, Any]]:
        """Apply an autotuner knob change at a clean boundary.

        Safety argument (DESIGN.md §17): a lag change only takes effect
        when the deferred ring is EMPTY — every optimistic commit so far
        has been validated, so shrinking or growing the window cannot
        strand an unvalidated predicate or change which steps a pending
        fault rolls back. Mid-window calls return None (caller retries at
        the next flush); the same `__init__` clamps apply, so an executor
        without deferred support or an L0-retry recovery keeps lag 1 no
        matter what the tuner asks for. No-op changes return None without
        journaling; an applied transition is appended to `reconfigs` and
        journaled as a `reconfig` line (byte-for-byte via reconcile()).
        """
        if self._ring:
            return None
        changes: Dict[str, Any] = {}
        if validate_lag is not None:
            lag = max(int(validate_lag), 1)
            if lag > 1 and not getattr(self.executor, "supports_deferred",
                                       False):
                lag = 1
            if lag > 1 and isinstance(self.recovery, RetryRecovery):
                lag = 1
            if lag != self.validate_lag:
                changes["validate_lag"] = {"from": self.validate_lag,
                                           "to": lag}
                self.validate_lag = lag
                self.schedule = dataclasses.replace(self.schedule,
                                                    validate_lag=lag)
        if checkpoint_interval is not None:
            ci = max(int(checkpoint_interval), 0)
            if ci != self.schedule.checkpoint_interval:
                changes["checkpoint_interval"] = {
                    "from": self.schedule.checkpoint_interval, "to": ci}
                self.schedule = dataclasses.replace(
                    self.schedule, checkpoint_interval=ci)
                if hasattr(self.recovery, "interval"):
                    self.recovery.interval = ci
        if tier_schedule is not None:
            tiers = getattr(self.recovery, "tiers", None)
            if tiers is not None and tiers.schedule != tier_schedule:
                changes["tier_schedule"] = {
                    "from": dataclasses.asdict(tiers.schedule),
                    "to": dataclasses.asdict(tier_schedule)}
                tiers.schedule = tier_schedule
        if not changes:
            return None
        rec = {"kind": "reconfig", "step": int(self.validated_frontier),
               "reason": str(reason), "changes": changes}
        self.reconfigs.append(rec)
        obs.note_reconfig(rec)
        return rec

    def init_dual(self):
        if self.init_fn is None:
            raise RuntimeError("engine has no init_fn")
        return self.init_fn()

    # -- the protected step --------------------------------------------------

    def run_protected_step(self, dual, batch, step: int) -> StepOutcome:
        """Execute one redundant step at `step`: inject (if armed) ->
        execute replicas -> TDC commit gate (immediate or deferred) -> FSC
        validation boundary -> checkpoint boundary. Returns the state to
        continue from plus the detection event, if any (feed it to
        `on_detection`)."""
        armed = jnp.asarray(
            1 if (self.inj_flag is not None
                  and self.inj_flag.arm_spec(self.inj_spec) is not None)
            else 0, jnp.bool_)
        compare = self.schedule.commit_due(step)

        if self.validate_lag > 1:
            return self._run_deferred(dual, batch, step, armed, compare)

        dual2, aux, event = self.executor.execute(dual, batch, step, armed,
                                                  compare)
        self._mark_injected(step)
        if event is not None:
            return StepOutcome(dual=dual2, aux=aux, event=event)
        # the step committed: consecutive-failure budgets reset (whatever
        # failed before was transient)
        note = getattr(self.recovery, "note_success", None)
        if note is not None:
            note()
        if compare:
            self.validated_frontier = step + 1

        new_step = step + 1
        if self.executor.can_validate and \
                self.schedule.validate_due(new_step):
            with obs.span("validate", step=new_step):
                event = self.executor.validate(dual2, new_step)
            if event is not None:
                return StepOutcome(dual=dual2, aux=aux, event=event)

        # checkpoint boundary (right after validation — minimal window of
        # vulnerability, paper Sec. 3.2)
        event = self._maybe_checkpoint(dual2, new_step)
        return StepOutcome(dual=dual2, aux=aux, event=event)

    def _run_deferred(self, dual, batch, step: int, armed,
                      compare: bool) -> StepOutcome:
        """Zero-sync hot path: the commit is optimistic, the match predicate
        joins the device-resident ring, and the host only reads the ring
        back every `validate_lag` commits or at a validate/checkpoint
        boundary. A fault-free steady-state step performs NO device->host
        transfer (asserted by tests via `hostsync.count_transfers`)."""
        dual2, aux, pred = self.executor.execute_deferred(dual, batch, step,
                                                          armed, compare)
        self._mark_injected(step)
        if compare:
            self._ring.append((step, pred))
        if self.emission_ring is not None:
            # park BEFORE the flush check below, so the window's last tick
            # is in the ring when its own predicate flushes — the emission
            # refs are the step's existing outputs (no launch, no readback)
            self.emission_ring.park(step, aux)

        new_step = step + 1
        # a DURABLE checkpoint tier due at new_step also forces the flush
        # (§11 retention rule extended to the hierarchy); pure device-ring
        # saves do not — they snapshot optimistically inside the window
        sync_due = getattr(self.recovery, "sync_due", None)
        boundary_due = (self.schedule.validate_due(new_step)
                        or self.schedule.checkpoint_due(new_step)
                        or (sync_due is not None and sync_due(new_step)))
        if len(self._ring) >= self.validate_lag or boundary_due:
            event = self.flush_deferred()
            if event is not None:
                return StepOutcome(dual=dual2, aux=aux, event=event)
            note = getattr(self.recovery, "note_success", None)
            if note is not None:
                note()

        if self.executor.can_validate and \
                self.schedule.validate_due(new_step):
            with obs.span("validate", step=new_step):
                event = self.executor.validate(dual2, new_step)
            if event is not None:
                return StepOutcome(dual=dual2, aux=aux, event=event)

        event = self._maybe_checkpoint(dual2, new_step)
        return StepOutcome(dual=dual2, aux=aux, event=event)

    def flush_deferred(self, final: bool = False, extra: Sequence = ()
                       ) -> Optional[DetectionEvent]:
        """Force the deferred-window readback: ONE host read of the combined
        ring predicate; only a failed flush pays a second read to localize
        the first mismatched step. Clean flush advances the validated
        frontier. Drivers call this at end of run; the engine calls it every
        `validate_lag` commits and before validate/checkpoint boundaries.

        With an `emission_ring` attached (DESIGN.md §18) the drained token
        window rides in the SAME `batched_get` as the combined predicate
        (label `token_emit`: one 3-item batch per D commits replaces 2·D
        per-tick emission reads); a failed flush truncates the ring at
        `slot_first_bad` BEFORE delivery, so rolled-back slots retract
        their un-drained tokens by construction. `final=True` forces the
        drain even below the ring's cadence (end of run). `extra` device
        arrays ride in the same readback (a caller's end-of-run counters);
        their host values land in `extra_values`, read alone only when the
        flush has nothing else to read."""
        emis = self.emission_ring
        drain = emis.provide(final=final) if emis is not None else None
        extra = list(extra)
        n_extra = len(extra)

        def split(vals):
            if n_extra:
                self.extra_values = list(vals[len(vals) - n_extra:])
                return vals[:len(vals) - n_extra]
            return vals

        if not self._ring:
            if drain is not None:
                # nothing pending validation: every parked row was already
                # proven clean by an earlier flush — pure delivery
                with obs.span("token_drain", rows=len(emis)):
                    vals = split(hostsync.batched_get(drain + extra,
                                                      label="token_emit"))
                _deliver(emis, vals)
            elif extra:
                split(hostsync.batched_get(extra, label="run_counters"))
            return None
        steps_, preds = zip(*self._ring)
        drain_vals = None
        if drain is not None or extra:
            with obs.span("deferred_flush", steps=len(self._ring),
                          drain_rows=len(emis) if drain is not None else 0):
                vals = split(hostsync.batched_get(
                    [jnp.all(jnp.stack(list(preds)))] + (drain or []) + extra,
                    label="token_emit" if drain is not None
                    else "deferred_flush"))
            ok = bool(np.all(vals[0]))
            if drain is not None:
                drain_vals = vals[1:]
        else:
            with obs.span("deferred_flush", steps=len(self._ring)):
                ok = hostsync.read_bool(jnp.all(jnp.stack(list(preds))),
                                        label="deferred_flush")
        if ok:
            self.validated_frontier = steps_[-1] + 1
            self._ring.clear()
            if drain_vals is not None:
                _deliver(emis, drain_vals)
            return None
        vals = hostsync.batched_get(list(preds), label="deferred_ring")
        bad = [s for s, v in zip(steps_, vals) if not bool(np.all(v))]
        detected_at = steps_[-1] + 1
        self._ring.clear()
        detail = {"detected_at": detected_at, "lag": detected_at - bad[0],
                  "faulty_steps": bad[:8]}
        # slot-granular localization (DESIGN.md §13): vector predicates
        # carry one bool per sequence slot, so a failed flush also reports
        # WHICH slots diverged and at which step each first went bad — the
        # per-request recovery rolls back only those slots
        slot_first: Optional[Dict[int, int]] = None
        if any(np.ndim(v) for v in vals):
            slot_first = {}
            for s, v in zip(steps_, vals):
                v = np.asarray(v)
                if v.ndim and not v.all():
                    for i in np.nonzero(~v)[0]:
                        slot_first.setdefault(int(i), s)
            detail["slots"] = sorted(slot_first)
            detail["slot_first_bad"] = slot_first
        if emis is not None:
            emis.truncate(slot_first, global_bad=bad[0])
            if drain_vals is not None:
                _deliver(emis, drain_vals)
        return DetectionEvent(step=bad[0], boundary="deferred", effect="TDC",
                              detail=detail)

    def validate_final(self, dual, step: int) -> Optional[DetectionEvent]:
        """Final-results comparison (paper Sec. 3.1); the event is tagged
        boundary='final' so NMR repair still applies. Flushes the deferred
        window first — unvalidated optimistic commits must not reach the
        final comparison unexamined."""
        event = self.flush_deferred()
        if event is not None:
            return event
        if not self.executor.can_validate_final:
            return None
        event = self.executor.validate(dual, step)
        if event is not None:
            event.boundary = "final"
        return event

    # -- detection handling ---------------------------------------------------

    def on_detection(self, event: DetectionEvent, dual):
        """Record + notify + recover. Returns the state to continue from;
        raises SedarSafeStop when the policy is (or degrades to) L1."""
        # predicates parked for steps at/after the detection are stale: the
        # recovery target predates them, and a restored trajectory re-runs
        # (and re-validates) those steps
        self._ring.clear()
        annotate = getattr(self.executor, "annotate_event", None)
        if annotate is not None:
            # lane -> device/host localization (DESIGN.md §16), attached
            # before the event is journaled or surfaced to callbacks
            annotate(event)
        self.detections.append(event)
        obs.note_detection(event)
        self.notify(event)

        fix = self.executor.repair(event, dual)
        if fix is not None:
            repaired, record = fix
            record = dict(record, at=event.step)
            self.recoveries.append(record)
            obs.note_recovery(record)
            return repaired

        action: RecoveryAction = self.recovery.on_detection(event)
        record = {"kind": action.kind, "step": action.step,
                  "rollbacks": action.rollbacks, "at": event.step}
        self.recoveries.append(record)
        # journal in a finally so the record goes out AFTER any restore
        # planner info is merged in — and even when safe-stop raises
        try:
            if action.kind == "stop":
                raise SedarSafeStop(event)
            if action.kind == "retry":
                return dual      # transient fault: re-execute the same step
            if action.kind == "restart_scratch":
                self.validated_frontier = 0
                return self.init_dual()
            if action.step is not None:
                self.validated_frontier = min(self.validated_frontier,
                                              action.step)
            if isinstance(self.recovery, ValidatedCheckpointRecovery):
                # L3 stores ONE validated state; re-seed every replica
                # from it
                with obs.span("rollback", step=action.step, kind=action.kind):
                    single = self.recovery.restore(
                        action, self.executor.primary(dual))
                    self._merge_restore_info(record)
                    single = jax.tree.map(jnp.asarray, single)
                    return self.executor.adopt_single(single)
            with obs.span("rollback", step=action.step, kind=action.kind):
                restored = self.recovery.restore(action, dual)
                self._merge_restore_info(record)
                return jax.tree.map(jnp.asarray, restored)
        finally:
            obs.note_recovery(record)

    def _merge_restore_info(self, record: Dict[str, Any]) -> None:
        """Fold the restore planner's outcome (tier, version, any corruption
        fallbacks — DESIGN.md §12) into the already-appended recovery
        record, so drivers report WHERE the state came back from."""
        info = getattr(self.recovery, "last_restore_info", None)
        if info:
            record.update(info)

    # -- internals ------------------------------------------------------------

    def _mark_injected(self, step: int) -> None:
        # persistent (stuck-bit) specs are never marked: the fault
        # re-manifests on every step by definition, so recovery
        # re-executions MUST re-inject (DESIGN.md §13 rejection path)
        if (self.inj_spec is not None and self.inj_flag is not None
                and not getattr(self.inj_spec, "persistent", False)
                and not self.inj_flag.already_injected()
                and step == self.inj_spec.step):
            self.inj_flag.mark()

    def _maybe_checkpoint(self, dual, step: int) -> Optional[DetectionEvent]:
        r = self.recovery
        if isinstance(r, MultiCheckpointRecovery):
            if step == 0 or not r.due(step):
                # the cadence check runs HERE so the off-boundary steps do
                # not pay the state-fingerprint readback (it used to sync
                # every step just to hand maybe_checkpoint an unused array)
                return None
            # fingerprint readback only when a manifest-writing tier saves:
            # a device-ring snapshot (tiered L2, every step) stays sync-free
            fp = hostsync.read_scalar(self.executor.state_fp(dual),
                                      label="checkpoint_fp") \
                if r.fp_needed(step) else None
            with obs.span("checkpoint", step=step):
                if r.maybe_checkpoint(step, dual, fp,
                                      validated_floor=self.validated_frontier):
                    self.checkpoints.append(step)
                    obs.note_checkpoint(step)
            return None
        if isinstance(r, ValidatedCheckpointRecovery):
            if step == 0 or step % r.interval != 0:
                return None
            fp0, fp_equal = self.executor.validated_fp(dual)
            with obs.span("checkpoint", step=step):
                ev = r.maybe_checkpoint(step,
                                        {"r0": self.executor.primary(dual)},
                                        fp0, fp_equal=fp_equal)
            if ev is None:
                self.checkpoints.append(step)
                obs.note_checkpoint(step)
            return ev
        return None   # SafeStop / RetryRecovery store no checkpoints
