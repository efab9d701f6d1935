"""Serving drivers over the unified SEDAR engine.

Two loops share the engine, the model and the detection machinery:

`generate()` — the original synchronous whole-batch loop (DESIGN.md §8):
decoding is deterministic (greedy), so a dual-replica serve step compares
logits fingerprints before emitting tokens — "validate the message before
sending it to the user". Every sequence in the batch advances in lockstep;
one corrupted replica compare stalls (or, under deferral, rolls back)
EVERY sequence in flight, and a retry-budget exhaustion safe-stops the
whole stream (the paper's L1 applied to the run).

`serve()` — continuous-batching protected decode (DESIGN.md §13): a
`SlotScheduler` packs independent requests into N sequence slots, each
carrying its own KV-cache slice, token and position. The engine's
protected step runs over the PACKED batch with a PER-SLOT fingerprint, so
`DetectionEvent`s are localized to sequence slots and the paper's recovery
levels re-scope from "the run" to "the request":

  * transient slot mismatch  -> partial commit + per-slot re-execution
    (L0 retry for one sequence; the other slots stream on),
  * deferred-window fault    -> rollback of ONLY the affected slots from a
    Tier-0 `SlotRing` (keyed device-resident snapshots, zero disk reads,
    zero host syncs — the PR-4 tier machinery per request),
  * exhausted slot budget    -> per-REQUEST rejection with notification
    (L1 safe-stop scoped to one sequence; the server keeps serving).

The fault-free hot path keeps the §11 zero-sync property — and extends it
through emission (DESIGN.md §18): with `validate_lag >= D` a decode tick
performs NO device->host transfer at all. Tokens park in the engine's
device-resident TokenRing and leave in ONE `batched_get` per flush window,
fused with the combined commit predicate (`token_emit` syncs are O(1/D),
asserted via `hostsync.count_transfers`); a detokenize consumer thread
streams them while the next window launches. Tier-0 snapshots/rollbacks
never touch disk (`checkpoint.count_disk_reads`).

Replica-free serving: the abft/hybrid backends guard every decode step's
logits block with a full-checksum ABFT pass (`_logits_checksum_guard`):
single-element corruption in the kernel-domain window is forward-corrected
and the corrected commit EMITS its token — no re-execution, rollbacks=0.

DMR attribution limit (unchanged from §8): with two replicas a PERSISTENT
state divergence cannot be attributed to the faulty replica. In the
continuous loop that degradation is per-request — after `max_retries`
consecutive failed re-executions of a slot, that REQUEST is rejected
rather than ever emitting an unvalidated token; the server itself never
dies (the paper's L1 guarantee, re-scoped).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import RunConfig
from repro.core import hostsync
from repro.core.detection import DetectionEvent, SedarSafeStop
from repro.core.engine import BoundarySchedule, SedarEngine
from repro.core.fingerprint import (pytree_fingerprint,
                                    pytree_fingerprint_fused,
                                    tensor_fingerprint)
from repro.core.injection import (InjectionSpec, MemoryInjectionFlag,
                                  flip_bit, inject_tree, make_kernel_fault,
                                  spec_step_hit)
from repro.core.policy import make_engine
from repro.core.recovery import RetryRecovery, SlotRecovery
from repro.models import build_model


@dataclass
class ServeReport:
    tokens_emitted: int = 0
    detections: List[DetectionEvent] = field(default_factory=list)
    retries: int = 0
    stopped: bool = False          # retry budget exhausted (safe stop)
    wall_s: float = 0.0


@dataclass
class BatchServeReport:
    """Outcome of one continuous-batching `serve()` run."""

    tokens_emitted: int = 0        # tokens delivered by COMPLETED requests
    steps: int = 0                 # protected decode steps executed
    wall_s: float = 0.0
    detections: List[DetectionEvent] = field(default_factory=list)
    retries: int = 0               # per-slot re-executions (L0)
    rollbacks: int = 0             # slot restores from the Tier-0 ring
    truncated_tokens: int = 0      # optimistic tokens rolled back + redone
    completed: List[int] = field(default_factory=list)   # request ids
    rejected: List[int] = field(default_factory=list)    # request ids
    stopped: bool = False
    prefill_packs: int = 0         # packed prefill launches (incl. retries)
    prefill_retries: int = 0       # per-prompt prefill re-executions
    # expert counters of models that count them (`serve()` docstring):
    # {"prefill"|"decode": {"routes", "routes_held", "rows"}}
    expert_counts: Dict[str, Dict[str, int]] = field(default_factory=dict)

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_emitted / max(self.wall_s, 1e-9)

    @property
    def goodput_tokens_per_step(self) -> float:
        """Delivered tokens per protected step — the wall-clock-free
        continuous-batching figure of merit (a synchronous wave loop burns
        steps decoding slots whose requests already finished)."""
        return self.tokens_emitted / max(self.steps, 1)


# expert counters, in this order wherever they travel as a flat list
COUNTERS = ("routes", "routes_held", "rows")


def _expert_counts(values) -> Dict[str, Dict[str, int]]:
    """Host values of the final readback's counters (decode's three, then
    each prefill launch's three) -> {"prefill", "decode"} totals."""
    vals = [int(v) for v in values]
    n = len(COUNTERS)
    return {"decode": dict(zip(COUNTERS, vals[:n])),
            "prefill": {k: sum(vals[n + i::n]) for i, k in
                        enumerate(COUNTERS)}}


# The serving loop's programs carry fixed names: jit names each module
# `jit_<function name>`, and the profiler's `XLA Modules` line shows it.

@functools.partial(jax.jit, static_argnames=("cache_axis",))
def sedar_slot_write(state, slot, cache_sl, tok_sl, pos_sl, active, *,
                     cache_axis: int):
    """One fused scatter of a slot's image (`slot_image`: cache leaves
    (L, 1, T, ...)) into the packed state at a dynamic slot index, along
    the cache's slot axis `cache_axis`. Jitted module-level so
    admissions/rollbacks cost one dispatch per replica instead of one per
    cache leaf."""
    cache = jax.tree.map(
        lambda full, s: jax.lax.dynamic_update_index_in_dim(
            full, s.astype(full.dtype), slot, cache_axis),
        state["cache"], cache_sl)
    return {**state, "cache": cache,
            "tok": state["tok"].at[slot].set(tok_sl.astype(jnp.int32)),
            "pos": state["pos"].at[slot].set(pos_sl.astype(jnp.int32)),
            "active": state["active"].at[slot].set(active)}


@jax.jit
def sedar_set_active(state, slot, value):
    return {**state, "active": state["active"].at[slot].set(value)}


@functools.partial(jax.jit, static_argnames=("cache_axis",))
def sedar_pack_insert(state, slots, sel, rows, toks, poss, *,
                      cache_axis: int):
    """Vectorized admission scatter: pack rows `sel` of a protected prefill
    launch land in slots `slots` of the packed state in ONE fused program
    (maxtext's prefill_insert_batch shape) — cache rows, first tokens,
    positions and the active mask together, instead of one `sedar_slot_write`
    dispatch per admitted request. The rows come as slot images (row i:
    (L, 1, T, ...)); a layer-major cache takes them onto its slot axis
    `cache_axis` inside this program."""
    idx = (slice(None),) * cache_axis + (slots,)

    def put(full, r):
        r = r[sel].astype(full.dtype)
        if cache_axis:
            r = jnp.moveaxis(jnp.squeeze(r, cache_axis + 1), 0, cache_axis)
        return full.at[idx].set(r)

    cache = jax.tree.map(put, state["cache"], rows)
    return {**state, "cache": cache,
            "tok": state["tok"].at[slots].set(toks[sel].astype(jnp.int32)),
            "pos": state["pos"].at[slots].set(poss[sel].astype(jnp.int32)),
            "active": state["active"].at[slots].set(
                jnp.ones(slots.shape, jnp.bool_))}


def _logits_checksum_guard(logits, spec: Optional[InjectionSpec],
                           step, armed):
    """ABFT output guard over one decode step's logits block — shared with
    the packed-prefill guard; see `abft.executor.logits_checksum_guard`."""
    from repro.abft.executor import logits_checksum_guard
    return logits_checksum_guard(logits, spec, step, armed)


class SedarServer:
    """Prefill once, then decode step-by-step (optionally dual-executed)."""

    def __init__(self, run_cfg: RunConfig, dual: bool = False,
                 inj_spec: Optional[InjectionSpec] = None,
                 max_retries: int = 8, backend: Optional[str] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 max_pack: int = 4):
        self.cfg = run_cfg
        self.model = build_model(run_cfg.model)
        self.dual = dual
        self.inj_spec = inj_spec
        self.inj_flag = MemoryInjectionFlag()
        self.max_retries = max_retries
        self._prefill = jax.jit(self._prefill_fn, static_argnums=(2,))
        self._decode = jax.jit(self._decode_fn)
        # Serving boundaries: TDC commit gate on every decode step; no
        # checkpoint boundary (the only mutable state is the KV cache,
        # recomputable from the prompt — recovery is re-execution). The
        # replica-free backends ("abft"/"hybrid", DESIGN.md §10) serve from
        # ONE decode state; hybrid additionally re-fingerprints the resident
        # {cache, tok} at the FSC cadence to catch at-rest cache corruption
        # that checksummed kernels cannot see. "fused" (DESIGN.md §11) runs
        # both decode replicas in one launch — token emission itself is the
        # only per-step readback left.
        backend = backend or ("sequential" if dual else "none")
        self.backend = backend
        fsc_interval = (int(run_cfg.sedar.param_validate_interval)
                        if backend == "hybrid" else 0)
        self._fsc_interval = fsc_interval
        fp_tree = ((lambda s: {"cache": s["cache"], "tok": s["tok"]})
                   if backend in ("abft", "hybrid")
                   else (lambda s: {"tok": s["tok"]}))

        def sedar_state_fp(s):
            return pytree_fingerprint(fp_tree(s))

        def sedar_state_fp_fused(s):
            return pytree_fingerprint_fused(fp_tree(s))

        self._state_fp = jax.jit(sedar_state_fp)
        self._fast_state_fp = jax.jit(sedar_state_fp_fused)
        # continuous-batching engines, keyed (slots, max_len, lag): the
        # packed decode program depends on all three, and reusing the
        # engine across serve() calls reuses its jit cache
        self._batch_engines: Dict[Tuple[int, int, int],
                                  Tuple[SedarEngine, Any, SlotRecovery]] = {}
        self.engine: SedarEngine = make_engine(
            run_cfg.sedar,
            backend=backend,
            step_fn=self._decode,
            state_fp_fn=self._state_fp,
            fast_state_fp_fn=self._fast_state_fp,
            schedule=BoundarySchedule(
                commit_interval=1, validate_interval=fsc_interval,
                checkpoint_interval=0,
                toe_timeout_s=run_cfg.sedar.toe_timeout_s),
            recovery=RetryRecovery(max_retries=max_retries),
            inj_spec=inj_spec, inj_flag=self.inj_flag,
            notify=lambda e: None)
        # bucketed/packed AOT prefill (DESIGN.md §14): the default admission
        # path for the dense families; stateful/windowed/frontend families
        # (prefiller.supported False) keep the legacy exact-shape prefill
        from repro.runtime.prefill import BucketedPrefill
        self.prefiller = BucketedPrefill(
            self.model, backend=backend, inj_spec=inj_spec,
            inj_flag=self.inj_flag, buckets=prefill_buckets,
            max_pack=max_pack)

    def warmup_prefill(self, params, max_len: int, *,
                       plain_batches: Sequence[int] = (1,)) -> int:
        """AOT-compile every bucketed prefill program ahead of traffic.
        Returns the number of programs compiled (0 for unsupported
        families — they keep the legacy jit path)."""
        if not self.prefiller.supported:
            return 0
        return self.prefiller.warmup(params, max_len,
                                     plain_batches=plain_batches)

    def _prefill_fn(self, params, batch, max_len):
        return self.model.prefill(params, batch, max_len)

    def _decode_fn(self, state, params, replica_id, armed):
        """Engine step_fn: (decode state, params-as-batch, rid, armed) ->
        (candidate state, logits fingerprint, logits[, AbftReport])."""
        if (self.inj_spec is not None
                and self.inj_spec.target not in ("kernel", "prefill",
                                                 "prefill_kernel")):
            params = inject_tree(params, self.inj_spec, step=state["pos"],
                                 replica_id=replica_id, armed=armed)
        logits, cache = self.model.decode_step(params, state["cache"],
                                               state["tok"], state["pos"])
        report = None
        if self.backend in ("abft", "hybrid"):
            # replica-free detection: checksum-guard the logits block; a
            # forward-corrected commit advances the decode state and its
            # token is emitted (see generate()/serve() — no re-execution)
            logits, report = _logits_checksum_guard(
                logits, self.inj_spec, state["pos"], armed)
        fp = pytree_fingerprint_fused({"logits": logits})
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        cand = {"cache": cache, "tok": tok, "pos": state["pos"] + 1}
        if report is not None:
            return cand, fp, logits, report
        return cand, fp, logits

    def generate(self, params, prompt_batch: Dict[str, Any], steps: int,
                 max_len: Optional[int] = None
                 ) -> "tuple[np.ndarray, ServeReport]":
        rep = ServeReport()
        t0 = time.time()
        eng = self.engine
        eng.reset()
        self.inj_flag.reset()
        if isinstance(eng.recovery, RetryRecovery):
            eng.recovery.reset()
        B, S = prompt_batch["tokens"].shape
        P = (self.cfg.model.frontend_seq
             if (self.cfg.model.frontend and self.cfg.model.family == "vlm") else 0)
        max_len = max_len or (S + P + steps + 8)
        pre = None
        if (self.prefiller.supported
                and "frontend_embeds" not in prompt_batch):
            # bucketed path: pad to the bucket boundary so every prompt
            # length <= the ladder hits ONE precompiled program instead of
            # jitting `_prefill` per exact (prompt_len, max_len)
            pre = self.prefiller.prefill_padded(
                params, prompt_batch["tokens"], max_len)
        if pre is None:
            pre = self._prefill(params, prompt_batch, max_len)
        logits, cache = pre
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = [np.asarray(tok)]
        pos = S + P
        dual = eng.executor.init_dual(
            {"cache": cache, "tok": tok, "pos": jnp.asarray(pos, jnp.int32)})

        while len(out) < steps:
            outcome = eng.run_protected_step(dual, params, pos)
            dual = outcome.dual
            if outcome.event is not None:
                # validate-before-send: on a gated mismatch the token is NOT
                # emitted and the step re-executes via the engine's retry
                # policy. An ABFT-instrumented decode step (backend "abft"/
                # "hybrid") may instead COMMIT FORWARD through repair() —
                # the position check below emits the corrected token instead
                # of re-executing (covered by tests/test_serve_batched.py).
                try:
                    dual = eng.on_detection(outcome.event, dual)
                except SedarSafeStop:
                    rep.stopped = True
                    break
                if hostsync.read_int(eng.executor.peek(dual, "pos"),
                                     label="decode_pos") > pos:
                    out.append(hostsync.read_scalar(
                        eng.executor.peek(dual, "tok"), label="token_emit"))
                    pos += 1
                continue
            # token emission is the product — the ONE per-step readback the
            # serving hot path keeps (validated by the commit gate above)
            out.append(hostsync.read_scalar(eng.executor.peek(dual, "tok"),
                                            label="token_emit"))
            pos += 1

        rep.detections = list(eng.detections)
        rep.retries = sum(1 for r in eng.recoveries
                          if r["kind"] in ("retry", "vote_retry"))
        rep.tokens_emitted = len(out) * B
        rep.wall_s = time.time() - t0
        return np.stack(out, axis=1), rep

    # ------------------------------------------------------------------
    # Continuous-batching protected decode (DESIGN.md §13)
    # ------------------------------------------------------------------

    @functools.cached_property
    def decode_layout(self) -> str:
        """How the packed decode holds and steps its slots, read off the
        cache structure the model builds (DESIGN.md §13). "layer_major": a
        layer-stacked K/V or latent cache, whose leaves (L, B, T, ...) take
        the slots as their batch axis; every slot decodes in one batch with
        its own position, each layer writing its rows in place.
        "slot_vmap": per-group recurrent or window states, stacked one
        `init_cache(1, ...)` per slot and decoded under a vmap over slots."""
        cache = jax.eval_shape(lambda: self.model.init_cache(1, 8)[0])
        flat = all(not isinstance(v, dict) for v in cache.values())
        return "layer_major" if flat else "slot_vmap"

    @property
    def slot_axes(self) -> Dict[str, int]:
        """The slot axis of each packed-state entry that has one: the cache
        carries it on axis 1 when layer-major, every other entry on 0."""
        return {"cache": int(self.decode_layout == "layer_major"),
                "tok": 0, "pos": 0, "active": 0}

    def _gate_axes(self) -> Dict[str, int]:
        """The entries a slotted commit gate restores for a slot that
        failed. A layer-major cache is left out: a decode tick writes each
        slot's cache at one row only, the row at the slot's position, and a
        row at or past a slot's position is no part of its state (decode
        writes it before attending it, as it does the pads of a packed
        prefill). A failed slot keeps its position, so restoring `pos`
        restores its cache; the gate never selects, nor keeps a second copy
        of, the whole cache."""
        axes = dict(self.slot_axes)
        if self.decode_layout == "layer_major":
            del axes["cache"]
        return axes

    def packed_state(self, slots: int, max_len: int) -> Dict[str, Any]:
        """The empty packed decode state of `slots` sequence slots: the
        cache (`decode_layout`), each slot's next token, position and
        active flag, the decode tick, and the expert counters of models
        that count them."""
        if self.decode_layout == "layer_major":
            cache, _ = self.model.init_cache(slots, max_len)
        else:
            cache1, _ = self.model.init_cache(1, max_len)
            cache = jax.tree.map(lambda x: jnp.stack([x] * slots), cache1)
        state = {"cache": cache,
                 "tok": jnp.zeros((slots, 1), jnp.int32),
                 "pos": jnp.zeros((slots,), jnp.int32),
                 "active": jnp.zeros((slots,), jnp.bool_),
                 "t": jnp.asarray(0, jnp.int32)}
        if self.model.counts_experts:
            state["moe"] = {k: jnp.asarray(0, jnp.int32) for k in COUNTERS}
        return state

    def _make_packed_decode(self, n_slots: int):
        """Packed step_fn over N sequence slots, each with its own cache
        rows / token / position. Returns per-slot fingerprints (N, 4) so
        the slotted executors localize mismatches to slots. Inactive slots
        are excluded from the fingerprint (their rows are zeroed) and their
        positions are frozen; their cache garbage is unobservable — a
        refill overwrites the whole slice at prefill. A layer-major cache
        decodes every slot in ONE batched model step with a position per
        row; other caches vmap the model step over slots."""
        spec = self.inj_spec
        abft_guard = self.backend in ("abft", "hybrid")
        model = self.model
        batched = self.decode_layout == "layer_major"

        def model_step(params, cache, tok, pos, act):
            kw = {"stats": True} if model.counts_experts else {}
            if batched:
                if model.counts_experts:
                    kw["real"] = act
                return model.decode_step(params, cache, tok[:, 0], pos, **kw)
            out = jax.vmap(lambda c, tk, p: model.decode_step(
                params, c, tk, p, **kw))(cache, tok, pos)
            if model.counts_experts:
                # routes of inactive slots are not counted; their rows ran
                counts = {k: jnp.sum(v if k == "rows"
                                     else jnp.where(act, v, 0))
                          for k, v in out[2].items()}
                return out[0], out[1], counts
            return out

        def sedar_decode_step(state, params, replica_id, armed):
            t = state["t"]
            if spec is not None and spec.target not in (
                    "kernel", "slot", "prefill", "prefill_kernel"):
                params = inject_tree(params, spec, step=t,
                                     replica_id=replica_id, armed=armed)
            act = state["active"]
            out = model_step(params, state["cache"], state["tok"],
                             state["pos"], act)
            logits, cache = out[0].reshape(n_slots, -1), out[1]   # (N, V)
            if spec is not None and spec.target == "slot":
                # slot-localized SDC: flip one bit of ONE slot's logits
                # (spec.leaf_idx doubles as the slot index) on the chosen
                # replica — the per-slot fault the detection must localize
                fire = jnp.logical_and(
                    jnp.asarray(armed, jnp.bool_),
                    jnp.logical_and(
                        spec_step_hit(spec, t),
                        jnp.asarray(replica_id) == spec.replica))
                idx = spec.leaf_idx * logits.shape[-1] + spec.flat_idx
                logits = jnp.where(fire, flip_bit(logits, idx, spec.bit),
                                   logits)
            report = None
            if abft_guard:
                logits, report = _logits_checksum_guard(logits, spec, t,
                                                        armed)
            fp = jax.vmap(tensor_fingerprint)(logits)     # (N, 4)
            fp = jnp.where(act[:, None], fp, jnp.zeros_like(fp))
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            cand = {"cache": cache, "tok": tok,
                    "pos": jnp.where(act, state["pos"] + 1, state["pos"]),
                    "active": act, "t": t + 1}
            if model.counts_experts:
                cand["moe"] = {k: state["moe"][k] + out[2][k]
                               for k in COUNTERS}
            # aux = the emission pair: the engine's TokenRing parks these
            # refs per tick (DESIGN.md §18) — outputs the step computes
            # anyway, so parking adds no launch and no readback
            if report is not None:
                return cand, fp, (tok, cand["pos"]), report
            return cand, fp, (tok, cand["pos"])

        return sedar_decode_step

    def _batch_engine(self, slots: int, max_len: int, lag: int
                      ) -> Tuple[SedarEngine, Any, SlotRecovery]:
        key = (slots, max_len, lag)
        if key in self._batch_engines:
            return self._batch_engines[key]
        from repro.checkpoint.tiers import SlotRing
        ring = SlotRing(slots_per_key=4)
        recovery = SlotRecovery(ring, max_retries=self.max_retries)
        # the unprotected backend keeps no pre-step state, so its state is
        # donated and the decode writes the cache in place; the replica
        # backends keep the pre-step state for their gate (fused donates
        # inside its own program)
        step = jax.jit(self._make_packed_decode(slots),
                       donate_argnums=(0,) if self.backend == "none" else ())
        eng = make_engine(
            self.cfg.sedar,
            backend=self.backend,
            step_fn=step,
            state_fp_fn=self._state_fp,
            fast_state_fp_fn=self._fast_state_fp,
            schedule=BoundarySchedule(
                commit_interval=1, validate_interval=self._fsc_interval,
                checkpoint_interval=0,
                toe_timeout_s=self.cfg.sedar.toe_timeout_s,
                validate_lag=lag),
            recovery=recovery,
            inj_spec=self.inj_spec, inj_flag=self.inj_flag,
            notify=lambda e: None,
            slots=(self._gate_axes() if self.backend in ("sequential", "fused")
                   else None))
        self._batch_engines[key] = (eng, ring, recovery)
        return eng, ring, recovery

    # -- packed-state surgery (all device-side; no host syncs) ----------------

    def _write_slot(self, eng, dual, slot: int, sl, active: bool = True):
        """Write one slot slice into EVERY replica image (admission refill /
        rollback merge). One jitted device scatter per replica through
        `map_state`."""
        slot_d = jnp.asarray(slot, jnp.int32)
        cache_sl = jax.tree.map(jnp.asarray, sl["cache"])
        tok_sl = jnp.asarray(sl["tok"])
        pos_sl = jnp.asarray(sl["pos"])
        act = jnp.asarray(active, jnp.bool_)
        axis = self.slot_axes["cache"]
        dual = eng.executor.map_state(
            lambda st: sedar_slot_write(st, slot_d, cache_sl, tok_sl,
                                        pos_sl, act, cache_axis=axis), dual)
        eng.executor.note_external_update()
        return dual

    def _set_active(self, eng, dual, slot: int, value: bool):
        slot_d = jnp.asarray(slot, jnp.int32)
        val = jnp.asarray(value, jnp.bool_)
        dual = eng.executor.map_state(
            lambda st: sedar_set_active(st, slot_d, val), dual)
        eng.executor.note_external_update()
        return dual

    def _save_images(self, eng, dual, ring, version: int, slots) -> None:
        """ONE program launch cuts every slot's {cache, tok, pos} image out
        of the executor's resident state; the ring keeps the images of
        `slots` as they are (fresh buffers) and the rest are dropped."""
        images = eng.executor.slot_images(
            dual, {k: self.slot_axes[k] for k in ("cache", "tok", "pos")})
        ring.save_many(version, {slot: images[slot] for slot in slots})

    def _snapshot_slots(self, eng, dual, sched, ring, version: int,
                        slot_arrays: int) -> None:
        """Tier-0 per-slot snapshots at the deferred-validation cadence:
        every RUNNING slot's {cache, tok, pos} image enters its keyed
        device ring right after a clean flush — one program launch, zero
        disk reads, zero host syncs (the zero-sync property extends through
        per-request checkpointing, asserted by tests). One `save_many`
        batch per flush: the snapshot versions land exactly on the drain
        edges the emission ring delivers at, so a rollback target never
        predates a delivered token (DESIGN.md §18). `slot_arrays` is the
        number of device arrays in one slot's image."""
        running = sched.running_items()
        if not running:
            return
        with obs.span("slot_snapshot", at="flush", step=version,
                      slots=len(running), arrays=len(running) * slot_arrays,
                      launches=1):
            self._save_images(eng, dual, ring, version,
                              [slot for slot, _req in running])

    def _admit_slot(self, eng, dual, params, slot: int, req, t: int,
                    ring, ring_on: bool, max_len: int):
        """Prefill `req` into a freed slot mid-flight: B=1 prefill, device
        scatter into the packed state, admission snapshot (version = the
        admit tick, so a deferred fault in the very first window has a
        rollback target), and emission of the prefill token."""
        prompt = jnp.asarray(req.prompt[None, :], jnp.int32)
        with obs.span("prefill_pack", step=t, pack=1, packed=False):
            logits, cache = self._prefill(params, {"tokens": prompt},
                                          max_len)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # (1,)
        sl = {"cache": cache, "tok": tok,
              "pos": jnp.asarray(req.prompt_len, jnp.int32)}
        ring.evict(slot)           # never resurrect a previous tenant
        dual = self._write_slot(eng, dual, slot, sl, active=True)
        if ring_on:
            arrays = len(jax.tree.leaves(sl))
            with obs.span("slot_snapshot", at="admit", step=t, slots=1,
                          arrays=arrays, launches=arrays):
                ring.save(slot, t, sl)
        req.pos0 = req.prompt_len
        # the prefill token is single-execution (like generate()): the
        # replica-validated stream starts at the first decode step
        req.tokens.append(int(hostsync.read_scalar(
            tok, label="prefill_emit")[0]))
        req.token_times.append(time.time())
        return dual

    def _admit_pack(self, eng, dual, params, pairs, t: int, ring,
                    ring_on: bool, max_len: int, rep: BatchServeReport,
                    sched, notify, events: List[DetectionEvent],
                    counts: Optional[list] = None):
        """Protected packed admission (DESIGN.md §14): ONE prefill launch
        computes caches + first tokens + per-prompt lanes for the whole
        pack, ONE `batched_get` reads back {tokens, verdicts}, ONE fused
        scatter inserts the admitted rows, and the SlotRing admission
        snapshots cut in one batched pass. A faulty row (lane mismatch /
        uncorrectable checksum residual) is retried ALONE — the clean rows
        of the pack are admitted immediately — and a persistent fault
        exhausts the retry budget into a per-request rejection. Each
        launch's expert counters (replica 0's) are appended to `counts`."""
        spec = self.inj_spec
        for slot, _req in pairs:
            ring.evict(slot)       # never resurrect a previous tenant
        pairs = list(pairs)
        prompts = [r.prompt for _, r in pairs]
        need = list(range(len(pairs)))   # rows not yet admitted
        budget = self.max_retries
        while need:
            # retries RELAUNCH the original pack shape: a persistent (stuck
            # lane) fault must keep hitting the same occupant, not slide to
            # row 0 of a shrunken retry pack — already-admitted rows are
            # recomputed but not re-admitted
            with obs.span("prefill_pack", step=t, pack=len(pairs)):
                res = self.prefiller.protected_pack(params, prompts,
                                                    max_len, t)
            rep.prefill_packs += 1
            if counts is not None and res["moe"] is not None:
                counts.append(res["moe"])
            toks, verdicts = hostsync.batched_get(
                [res["tok"], res["verdict"]], label="prefill_emit")
            good = [i for i in need if int(verdicts[i]) != 0]
            bad = [i for i in need if int(verdicts[i]) == 0]
            if good:
                rows, toks_d, poss = res["rows"], res["tok"], res["lengths"]
                sel = jnp.asarray(good, jnp.int32)
                slots_d = jnp.asarray([pairs[i][0] for i in good], jnp.int32)
                axis = self.slot_axes["cache"]
                dual = eng.executor.map_state(
                    lambda st: sedar_pack_insert(st, slots_d, sel, rows,
                                                 toks_d, poss,
                                                 cache_axis=axis), dual)
                eng.executor.note_external_update()
                if ring_on:
                    # the flush edges' program, on the state the rows were
                    # just inserted into: no compile for the pack's size
                    with obs.span("slot_snapshot", at="admit", step=t,
                                  slots=len(good),
                                  arrays=len(good)
                                  * (len(jax.tree.leaves(rows)) + 2),
                                  launches=1):
                        self._save_images(eng, dual, ring, t,
                                          [pairs[i][0] for i in good])
                now_wall = time.time()
                for i in good:
                    _slot, req = pairs[i]
                    req.pos0 = req.prompt_len
                    # like the legacy path, the emitted prefill token is
                    # already past the detection contract: its row's lane
                    # (or checksum row) verified before this readback
                    req.tokens.append(int(toks[i, 0]))
                    req.token_times.append(now_wall)
            corrected = [i for i in good if int(verdicts[i]) == 2]
            if corrected:
                # prefill events never route through eng.on_detection (the
                # pack retries inline), so they are journaled HERE
                ev = DetectionEvent(
                    step=t, boundary="prefill", effect="abft_corrected",
                    detail={"slots": [pairs[i][0] for i in corrected],
                            "rids": [pairs[i][1].rid for i in corrected]})
                events.append(ev)
                obs.note_detection(ev)
            if (bad or corrected) and spec is not None and not spec.persistent:
                self.inj_flag.mark()   # paper's injected.txt: the transient
                # fault MANIFESTED (detected or forward-corrected) — it must
                # not re-fire on the retry or in a later stage
            if not bad:
                break
            ev = DetectionEvent(
                step=t, boundary="prefill", effect="TDC",
                detail={"slots": [pairs[i][0] for i in bad],
                        "rids": [pairs[i][1].rid for i in bad]})
            events.append(ev)
            obs.note_detection(ev)
            budget -= 1
            if budget <= 0:
                for i in bad:
                    slot, req = pairs[i]
                    sched.reject(slot, "prefill validation failed: "
                                 "consecutive retry budget exhausted")
                    rep.rejected.append(req.rid)
                    obs.note_rejection(t, rid=req.rid, slot=slot,
                                       reason="prefill_persistent")
                    if notify is not None:
                        notify(req, events[-1])
                break
            rep.prefill_retries += len(bad)
            need = bad
        return dual

    def _finish(self, sched, slot: int, rep: BatchServeReport) -> None:
        """Release a drained slot exactly once: release/reactivate cleared
        the slot (or flipped its status) before any second path — the final
        partial flush, `_release_drained` and the quiescence sweep — can
        reach it, so a no-longer-draining occupant is simply skipped."""
        req = sched.request(slot)
        if req is None or req.status != "draining":
            return
        req = sched.release(slot)
        rep.completed.append(req.rid)

    @staticmethod
    def _validated(eng, sched) -> List[int]:
        """Draining slots whose last step a flush has validated."""
        return [slot for slot, req in sched.draining_items()
                if eng.validated_frontier >= req.finish_step]

    @classmethod
    def _releasable(cls, eng, sched) -> List[int]:
        """Draining slots to release once no slot runs: the validated ones
        and, when no predicate awaits validation, every other drainer too
        (quiescence: their evidence either flushed clean or was consumed by
        an event that did not implicate them — nothing will ever re-examine
        them, and holding them would spin forever)."""
        ready = cls._validated(eng, sched)
        if not eng.pending_validation:
            ready += [slot for slot, _req in sched.draining_items()
                      if slot not in ready]
        return ready

    def _release_drained(self, eng, sched, rep: BatchServeReport) -> None:
        for slot in self._validated(eng, sched):
            self._finish(sched, slot, rep)

    def _handle_event(self, eng, recovery, sched, ring, event, dual,
                      rep: BatchServeReport, notify=None, expected=None,
                      consumer=None):
        """Per-request recovery: route the event through the engine (slot
        retry / ring restore), then apply the request-level consequences —
        token-stream truncation for rolled-back slots, eviction +
        notification for rejected requests, early release for draining
        slots a failed flush proved clean.

        Drain mode (`expected` is the host-side token-count map): the
        failed flush already retracted the faulty slots' un-drained rows
        from the emission ring, so there is no stream to truncate here —
        the restore just resets the slot's optimistic count to the restored
        position. The consumer is quiesced FIRST so rejection callbacks
        (and any reader of request streams) see the delivered prefix."""
        if consumer is not None:
            consumer.quiesce()
        try:
            dual = eng.on_detection(event, dual)
        except SedarSafeStop:
            rep.stopped = True
            return dual
        for slot in recovery.take_rejections():
            req = sched.request(slot)
            if req is not None:
                sched.reject(slot, "per-request safe stop: consecutive "
                             "retry budget exhausted")
                rep.rejected.append(req.rid)
                obs.note_rejection(event.step, rid=req.rid, slot=slot,
                                   reason="persistent_fault")
                if notify is not None:
                    notify(req, event)
            ring.evict(slot)
            if expected is not None:
                expected.pop(slot, None)
            dual = self._set_active(eng, dual, slot, False)
        for slot, info in recovery.take_restores().items():
            req = sched.request(slot)
            if req is None:
                continue
            rep.rollbacks += 1
            keep = max(info["pos"] - req.pos0 + 1, 1)
            if expected is not None:
                expected[slot] = keep
            elif len(req.tokens) > keep:
                cut = len(req.tokens) - keep
                req.truncated_tokens += cut
                rep.truncated_tokens += cut
                del req.tokens[keep:]
                del req.token_times[keep:]
            if req.status == "draining":
                sched.reactivate(slot)   # rollback reached its final window
        if event.boundary == "deferred":
            # the failed flush EXAMINED every parked predicate: draining
            # slots not implicated are proven clean through their final
            # step — release them now (the global frontier regressed to the
            # faulty step and would otherwise hold them hostage)
            bad = set(event.detail.get("slots", []))
            for slot, _req in list(sched.draining_items()):
                if slot not in bad:
                    self._finish(sched, slot, rep)
        return dual

    def serve(self, params, requests, *, slots: int = 4,
              max_len: Optional[int] = None, validate_lag: Optional[int] = None,
              queue_depth: int = 0, max_steps: Optional[int] = None,
              notify_reject=None, autotune=None,
              on_token=None, consumer_depth: int = 8):
        """Continuous-batching protected decode over an open-loop request
        stream. Mutates and returns the `Request` objects (lifecycle fields
        are reset first, so a template list can be replayed for fault-free
        twins) plus a `BatchServeReport`.

        `validate_lag` > 1 arms the deferred window: the fault-free decode
        step performs NO host sync (detection lags by <= D steps, and a
        detected fault rolls back only the affected slots from the Tier-0
        ring) — token emission itself is deferred to the flush cadence
        through the engine's TokenRing and streamed from a detokenize
        consumer thread (DESIGN.md §18), one drain per flush; at lag 1
        every tick reads its tokens back. `on_token(req, tok, index)`
        streams each delivered token (called from the consumer thread in
        drain mode);
        `consumer_depth` bounds the detokenize queue (backpressure).
        `queue_depth` bounds the admission queue (backpressure ->
        immediate rejection). `autotune` (a policy.Autotuner with
        mode="serve") live-retunes the lag at clean flush boundaries; the
        engine's reset() restores the configured lag for the next serve()
        call.

        Expert counters (models that count experts, `model.counts_experts`):
        on the device, packed prefill and decode each count the routes of
        real tokens over every routed expert (`routes`; pads, dummy pack
        rows and inactive slots left out), those that land on experts held
        here (`routes_held`), and the held-expert rows the layers ran (`rows`,
        pads and inactive slots included); replica 0's counts under a dual
        backend, re-executions included, the legacy exact-shape admission
        not. They ride in the final flush's readback, once per call, into
        `rep.expert_counts` and the `serve_finish` span's args."""
        from repro.runtime.emission import DetokenizeConsumer, TokenRing
        from repro.runtime.prefill import group_packs
        from repro.runtime.scheduler import (RUNNING, RequestQueue,
                                             SlotScheduler)
        if self.cfg.model.frontend:
            raise NotImplementedError(
                "continuous batching serves token-prompt families; frontend "
                "(VLM/audio) prompts need per-request embed plumbing")
        rep = BatchServeReport()
        t0 = time.time()
        # every host stage below opens its own span only when it has work;
        # no span covers a whole call, the tick loop or a whole tick
        with obs.span("serve_start", slots=slots,
                      requests=len(requests)) as start_args:
            for r in requests:
                r.status, r.slot = "pending", None
                r.tokens, r.token_times = [], []
                r.pos0, r.admit_step, r.finish_step = 0, None, None
                r.truncated_tokens, r.reject_reason = 0, ""
                r.arrival_time = None
            max_prompt = max((r.prompt_len for r in requests), default=8)
            max_new = max((r.max_new_tokens for r in requests), default=8)
            max_len = max_len or (max_prompt + max_new + 8)
            lag = int(validate_lag
                      if validate_lag is not None
                      else getattr(self.cfg.sedar, "validate_lag", 1))
            eng, ring, recovery = self._batch_engine(slots, max_len,
                                                     max(lag, 1))
            eng.reset()
            recovery.reset()
            self.inj_flag.reset()
            recovery.merge = lambda dual, slot, sl: self._write_slot(
                eng, dual, slot, sl, active=True)
            ring_on = eng.validate_lag > 1   # clamped lag => pre-commit gate
            # lag-aligned batched drain (DESIGN.md §18): tokens leave the
            # device through flush_deferred's fused readback and reach the
            # request streams via the consumer thread. Lag 1 emits per
            # tick: every commit there is already a sync point.
            drain_on = ring_on
            tokring = consumer = None
            expected: Dict[int, int] = {}   # slot -> optimistic token count
            if drain_on:
                consumer = DetokenizeConsumer(on_token=on_token,
                                              max_queue=consumer_depth).start()
                tokring = TokenRing(cadence=eng.validate_lag,
                                    sink=consumer.submit)
                eng.emission_ring = tokring

            sched = SlotScheduler(slots, RequestQueue(queue_depth))
            pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
            state = self.packed_state(slots, max_len)
            counting = self.model.counts_experts
            prefill_counts: Optional[list] = [] if counting else None
            if start_args is not None:
                start_args.update(
                    cache_bytes_slot=sum(
                        x.size * x.dtype.itemsize
                        for x in jax.tree.leaves(state["cache"])) // slots,
                    experts_held=(self.cfg.model.num_experts
                                  if counting else 0),
                    decode_layout=self.decode_layout)
            # device arrays in one slot's snapshot image: cache, tok, pos
            slot_arrays = len(jax.tree.leaves(state["cache"])) + 2
            dual = eng.executor.init_dual(state)

            use_packed = self.prefiller.supported
            prefill_events: List[DetectionEvent] = []
            t = 0
            cap = max_steps or (sum(r.max_new_tokens for r in requests)
                                + len(requests)) * 4 + 64
        while t < cap and (pending or len(sched.queue) or sched.busy):
            # the autotuner may have moved the lag at the last boundary
            ring_on = eng.validate_lag > 1
            while pending and pending[0].arrival <= t:
                req = pending.pop(0)
                req.arrival_time = time.time()     # TTFT reference stamp
                if not sched.queue.offer(req):
                    rep.rejected.append(req.rid)   # backpressure shed
            pairs = sched.admit(t)
            if pairs:
                packs, overflow = (group_packs(
                    pairs, [req.prompt_len for _, req in pairs],
                    self.prefiller.usable_buckets(max_len),
                    self.prefiller.max_pack) if use_packed else ([], pairs))
                with obs.span("admit", step=t, admitted=len(pairs),
                              packs=len(packs), overflow=len(overflow)):
                    for _bucket, chunk in packs:
                        dual = self._admit_pack(eng, dual, params, chunk, t,
                                                ring, ring_on, max_len, rep,
                                                sched, notify_reject,
                                                prefill_events,
                                                prefill_counts)
                    # longer than the ladder, or a family without packed
                    # prefill
                    for slot, req in overflow:
                        dual = self._admit_slot(eng, dual, params, slot, req,
                                                t, ring, ring_on, max_len)
                    for slot, req in pairs:
                        if req.status == RUNNING and drain_on:
                            # the prefill token was validated and delivered
                            # at admission; the optimistic count starts there
                            expected[slot] = 1
                        if (req.status == RUNNING
                                and len(req.tokens) >= req.max_new_tokens):
                            # budget of 1: the prefill token already fills
                            # it — its validation (if any) happened at
                            # admission, so release immediately
                            dual = self._set_active(eng, dual, slot, False)
                            sched.drain(slot, finish_step=t)
                            self._finish(sched, slot, rep)
            if not sched.running_items():
                if sched.draining_items():
                    ev = eng.flush_deferred()
                    if ev is not None:
                        dual = self._handle_event(
                            eng, recovery, sched, ring, ev, dual, rep,
                            notify_reject,
                            expected=expected if drain_on else None,
                            consumer=consumer)
                    ready = self._releasable(eng, sched)
                    if ready:
                        with obs.span("slot_release", step=t, drained=0,
                                      released=len(ready)):
                            for slot in ready:
                                self._finish(sched, slot, rep)
                    continue
                if pending or len(sched.queue):
                    # idle tick awaiting arrivals: advance the DEVICE decode
                    # tick too — state['t'] gates injection firing while the
                    # engine's once-only flag is marked on the DRIVER step,
                    # so letting the clocks drift would disarm a campaign's
                    # fault before the device ever reached its step
                    dual = eng.executor.map_state(
                        lambda st: {**st, "t": st["t"] + 1}, dual)
                    t += 1
                    continue
                break
            if drain_on:
                # owner snapshot for the rows this tick will park: the
                # ring copies it, so a later admission reusing the slot
                # cannot reroute this window's tokens
                tokring.owners = dict(sched.running_items())
            with obs.span("decode_tick", step=t):
                outcome = eng.run_protected_step(dual, params, t)
            dual = outcome.dual
            rep.steps += 1
            if drain_on:
                # host-side optimistic accounting — no readback: every
                # running slot's device position advanced by one (a frozen
                # fused slot over-counts until its flush event resets the
                # count from the restored position)
                for slot, _req in sched.running_items():
                    expected[slot] = expected.get(slot, 1) + 1
            if outcome.event is not None:
                dual = self._handle_event(
                    eng, recovery, sched, ring, outcome.event, dual, rep,
                    notify_reject, expected=expected if drain_on else None,
                    consumer=consumer)
            elif ring_on and not eng.pending_validation:
                # clean flush boundary: cut the Tier-0 per-slot snapshots
                self._snapshot_slots(eng, dual, sched, ring, t + 1,
                                     slot_arrays)
            if autotune is not None:
                autotune.maybe_tune(eng, t + 1)
                if drain_on and eng.validate_lag == 1:
                    # the tuner left deferred mode (reconfig applies only
                    # at a clean boundary, so the predicate ring is empty):
                    # deliver everything parked and drop back to per-tick
                    # emission — the lag-1 path never parks
                    eng.flush_deferred(final=True)
                    consumer.quiesce()
                    eng.emission_ring = None
                    drain_on = False
            if drain_on:
                # flush-edge semantics: budget decisions ride the host
                # count, tokens surface through the consumer at the drain
                # cadence, and drained slots release when a flush moved
                # the validated frontier past their finish step
                done = [slot for slot, req in sched.running_items()
                        if expected.get(slot, 1) >= req.max_new_tokens]
                for slot in done:
                    sched.drain(slot, finish_step=t + 1)
                ready = ([] if eng.pending_validation
                         else self._validated(eng, sched))
                if done or ready:
                    with obs.span("slot_release", step=t, drained=len(done),
                                  released=len(ready)):
                        for slot in done:
                            dual = self._set_active(eng, dual, slot, False)
                        for slot in ready:
                            self._finish(sched, slot, rep)
            else:
                # per-tick emission (lag 1):
                # tok + pos fetched in a single transfer batch; per-slot
                # position deltas drive emission, so partial commits
                # (faulty slot frozen) and rollbacks (position regressed)
                # need no special-casing here
                running = sched.running_items()
                with obs.span("token_emit", step=t, slots=len(running)):
                    toks, poss = hostsync.batched_get(
                        [eng.executor.peek(dual, "tok"),
                         eng.executor.peek(dual, "pos")], label="token_emit")
                    now_wall = time.time()
                    for slot, req in running:
                        target = int(poss[slot]) - req.pos0 + 1
                        if target == len(req.tokens) + 1:
                            req.tokens.append(int(toks[slot, 0]))
                            req.token_times.append(now_wall)
                            obs.note_tokens(1)
                            if on_token is not None:
                                on_token(req, req.tokens[-1],
                                         len(req.tokens) - 1)
                        if len(req.tokens) >= req.max_new_tokens:
                            sched.drain(slot, finish_step=t + 1)
                            dual = self._set_active(eng, dual, slot, False)
                            if eng.validate_lag == 1:
                                # immediate mode: every emitted token passed
                                # the commit gate (emission follows committed
                                # position deltas), so the stream is already
                                # validated even if ANOTHER slot's event kept
                                # the global frontier behind — release now
                                self._finish(sched, slot, rep)
                    self._release_drained(eng, sched, rep)
            t += 1

        with obs.span("serve_finish", step=t,
                      draining=len(sched.draining_items())) as finish_args:
            # final flush: validates (and in drain mode DRAINS) the partial
            # window left when the loop exits — `final=True` forces the
            # drain below the cadence so no token stays parked past the run;
            # the expert counters ride in its readback
            extra = []
            if counting:
                moe = eng.executor.peek(dual, "moe")
                extra = [moe[k] for k in COUNTERS] + [
                    c[k] for c in prefill_counts for k in COUNTERS]
            ev = eng.flush_deferred(final=True, extra=extra)
            if counting:
                rep.expert_counts = _expert_counts(eng.extra_values)
                if finish_args is not None:
                    finish_args.update(
                        {f"moe_{k}_{phase}": v for phase, c in
                         rep.expert_counts.items() for k, v in c.items()})
            if ev is not None:
                dual = self._handle_event(
                    eng, recovery, sched, ring, ev, dual, rep, notify_reject,
                    expected=expected if drain_on else None,
                    consumer=consumer)
            # `_finish` skips slots already released by the final flush's
            # delivery path, so a drainer finishing inside the final partial
            # window releases exactly once (no duplicate, none stranded)
            for slot in self._releasable(eng, sched):
                self._finish(sched, slot, rep)
            if consumer is not None:
                consumer.quiesce()
                consumer.close()
                eng.emission_ring = None
                # ring retraction replaced driver-side truncation: aggregate
                # the per-request counts the consumer accumulated
                rep.truncated_tokens = sum(r.truncated_tokens
                                           for r in requests)

        rep.detections = prefill_events + list(eng.detections)
        rep.retries = sum(1 for r in eng.recoveries if r["kind"] == "retry")
        rep.tokens_emitted = sum(len(r.tokens) for r in requests
                                 if r.status == "done")
        rep.wall_s = time.time() - t0
        return requests, rep
