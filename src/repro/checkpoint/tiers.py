"""Tiered checkpoint hierarchy — device / host / disk / partner (DESIGN.md §12).

The paper's "different Levels of Checkpointing" (L2/L3) say WHAT a
checkpoint means; this module adds WHERE it lives. Aupy et al.
(arXiv:1310.8486) show the optimal silent-error strategy couples the
verification cadence with a *hierarchy* of checkpoint costs — so the
hierarchy is:

  Tier 0  `device`   on-device snapshot ring: pure `jnp.copy` per leaf, no
                     D2H, no serialization. Rollback is instant and performs
                     ZERO disk reads and ZERO host syncs. Survives nothing
                     but the process (an SDC in the step, the common case).
  Tier 1  `host`     host-RAM ring: ONE batched D2H per save (hostsync),
                     no serialization. Survives device-state loss.
  Tier 2  `disk`     the async atomic `CheckpointStore` (optionally
                     `DeltaCheckpointStore` / compressed). Survives process
                     death.
  Tier 3  `partner`  a second directory with independently computed
                     digests — the fallback when a Tier-2 restore raises
                     `CheckpointCorruptionError`. Survives single-store
                     corruption (bit rot, torn volumes).

`TieredCheckpointer` is the single facade: per-tier save cadences
(`TierSchedule`), one shared D2H transfer feeding every durable tier, and a
cost-aware restore planner (`plan` / `restore`) that picks the cheapest
tier holding a valid version at-or-below the caller's bound, falling back
tier-by-tier (and then version-by-version) on corruption — recorded as
events, never silently.

Ring tiers intentionally hold versions INSIDE the deferred-validation
window (they are disposable; the planner's `max_step` bound filters them),
while the durable tiers keep the §11 invariant of only being cut after a
clean flush. Ring eviction honors the same `keep_floor` anchor as
`CheckpointStore.gc_keep_last`.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint.store import CheckpointCorruptionError, CheckpointStore

TIER_ORDER = ("device", "host", "disk", "partner")

# Relative restore-cost weights for the planner (unitless; only ratios
# matter). A device slot is a few on-device copies; host pays one H2D
# upload; disk pays deserialization + digest verification; partner is disk
# plus being the last line of defense. `rework_weight` prices one step of
# lost progress — so a ring slot `k` steps older than a disk version wins
# until the rework gap outgrows the deserialization saving. Callers can
# override with measured costs (temporal_model.TierCosts models them in
# hours).
DEFAULT_RESTORE_COSTS = {"device": 1.0, "host": 4.0,
                         "disk": 64.0, "partner": 96.0}
DEFAULT_REWORK_WEIGHT = 1.0


@dataclass(frozen=True)
class TierSchedule:
    """Per-tier save cadence in steps; 0 disables the tier."""

    device: int = 0
    host: int = 0
    disk: int = 0
    partner: int = 0

    def interval(self, tier: str) -> int:
        return int(getattr(self, tier))

    def tier_due(self, tier: str, step: int) -> bool:
        iv = self.interval(tier)
        return iv > 0 and step > 0 and step % iv == 0

    def enabled(self) -> Tuple[str, ...]:
        return tuple(t for t in TIER_ORDER if self.interval(t) > 0)


class _Ring:
    """Bounded newest-last version ring shared by the device/host tiers.

    Eviction honors `keep_floor` exactly like `gc_keep_last`: the newest
    slot at-or-below the floor (the last version older than every
    unvalidated step) is pinned, so a deferred-window fault always finds an
    in-ring rollback target even after the ring rotates past it."""

    def __init__(self, slots: int):
        self.slots = max(int(slots), 1)
        self._ring: List[Tuple[int, Any]] = []

    def _put(self, step: int, payload, keep_floor: Optional[int]) -> None:
        self._ring = [e for e in self._ring if e[0] != step]
        self._ring.append((step, payload))
        self._ring.sort(key=lambda e: e[0])
        while len(self._ring) > self.slots:
            anchored = [s for s, _ in self._ring
                        if keep_floor is not None and s <= keep_floor]
            anchor = max(anchored) if anchored else None
            victim = next((i for i, (s, _) in enumerate(self._ring)
                           if s != anchor), None)
            if victim is None:
                break
            del self._ring[victim]

    def _get(self, step: int):
        for s, payload in self._ring:
            if s == step:
                return payload
        raise KeyError(f"version {step} not in ring")

    def versions(self) -> List[int]:
        return [s for s, _ in self._ring]

    def has(self, step: int) -> bool:
        return any(s == step for s, _ in self._ring)

    def keep_only(self, step: int) -> None:
        self._ring = [e for e in self._ring if e[0] == step]

    def clear(self) -> None:
        self._ring = []


class DeviceRing(_Ring):
    """Tier 0: on-device snapshot ring. Saves and restores are pure
    device-side copies — the snapshot must be copied both ways because the
    live state's buffers may be DONATED by the next step (and a restored
    state's buffers likewise; the ring keeps its own)."""

    name = "device"

    def save(self, step: int, state,
             keep_floor: Optional[int] = None) -> None:
        self._put(step, jax.tree.map(jnp.copy, state), keep_floor)

    def restore(self, step: int):
        return jax.tree.map(jnp.copy, self._get(step))


class SlotRing:
    """Tier-0 KEYED snapshot ring for continuous-batching serving
    (DESIGN.md §13): one bounded device-resident version ring PER SEQUENCE
    SLOT, holding that slot's {cache slice, token, position} image.

    Same storage contract as `DeviceRing` — saves and restores are pure
    device work, ZERO disk reads and ZERO host syncs — but keyed by slot so
    a detected fault restores ONLY the affected sequence's state while the
    other slots' rings (and live state) are untouched. Versions are decode
    ticks; `restore(slot, max_step=k)` returns the newest snapshot at or
    below the faulty step, exactly like the planner's `max_step` bound
    filters post-fault versions out of recovery. Eviction on admission
    (`evict`) drops a finished/rejected request's history so the ring never
    resurrects state across requests sharing a slot."""

    name = "device"

    def __init__(self, slots_per_key: int = 4):
        self.slots_per_key = max(int(slots_per_key), 1)
        self._rings: Dict[int, _Ring] = {}
        self.saves = 0
        self.restores = 0

    def _put(self, key: int, step: int, state_slice) -> None:
        ring = self._rings.setdefault(int(key), _Ring(self.slots_per_key))
        ring._put(step, state_slice, keep_floor=None)
        self.saves += 1

    def save(self, key: int, step: int, state_slice) -> None:
        self._put(key, step, jax.tree.map(jnp.copy, state_slice))

    def save_many(self, step: int, slices: "Dict[int, Any]") -> None:
        """Batched snapshots at one shared version: a whole prefill pack's
        slot slices at admission (DESIGN.md §14), or every live slot at a
        clean flush edge under lag-aligned drain (DESIGN.md §18 — flush
        edges are the only points where the optimistic window is fully
        validated, so drain-mode versions always land there). The ring
        takes the slices as they are, with no copy: callers pass fresh
        buffers that no live (donated) state aliases, the outputs of the
        one-launch snapshot program. Zero disk, zero host syncs."""
        for key, sl in slices.items():
            self._put(key, step, sl)

    def newest_version(self, key: int) -> Optional[int]:
        """Newest recorded version for `key` (None when the slot has no
        history) — the version restore() would pick with no `max_step`
        bound, without paying its copy. Under lag-aligned drain every
        version is a clean flush edge, so this is also the slot's newest
        fully-validated point."""
        versions = self.versions(key)
        return max(versions) if versions else None

    def restore(self, key: int, max_step: Optional[int] = None
                ) -> Tuple[int, Any]:
        """Newest version at-or-below `max_step` for `key` ->
        (version, state slice copy). KeyError when nothing qualifies."""
        ring = self._rings.get(int(key))
        if ring is None:
            raise KeyError(f"no snapshots for slot {key}")
        cands = [s for s in ring.versions()
                 if max_step is None or s <= max_step]
        if not cands:
            raise KeyError(f"no slot-{key} snapshot at or below {max_step}")
        version = max(cands)
        self.restores += 1
        return version, jax.tree.map(jnp.copy, ring._get(version))

    def versions(self, key: int) -> List[int]:
        ring = self._rings.get(int(key))
        return ring.versions() if ring is not None else []

    def evict(self, key: int) -> None:
        self._rings.pop(int(key), None)

    def clear(self) -> None:
        self._rings.clear()


class HostRing(_Ring):
    """Tier 1: host-RAM ring. One batched D2H per save (counted through
    hostsync as `tier_host_save` unless the transfer is shared with the
    durable tiers); restore re-uploads without touching disk."""

    name = "host"

    def save(self, step: int, host_leaves: List[np.ndarray], treedef,
             keep_floor: Optional[int] = None) -> None:
        self._put(step, (list(host_leaves), treedef), keep_floor)

    def restore(self, step: int, template=None):
        leaves, treedef = self._get(step)
        if template is not None:
            tleaves = jax.tree_util.tree_flatten(template)[0]
            if len(tleaves) != len(leaves):
                raise ValueError(
                    f"host ring version {step} has {len(leaves)} leaves, "
                    f"template has {len(tleaves)}")
        return jax.tree_util.tree_unflatten(treedef, leaves)


class TieredCheckpointer:
    """Facade over the tier hierarchy: cadence-routed saves, one shared D2H
    batch for all durable tiers, cost-aware restore planning with
    corruption fallback, per-tier accounting."""

    def __init__(self, schedule: TierSchedule, *,
                 device_slots: int = 4, host_slots: int = 4,
                 disk_store: Optional[CheckpointStore] = None,
                 partner_store: Optional[CheckpointStore] = None,
                 restore_costs: Optional[Dict[str, float]] = None,
                 rework_weight: float = DEFAULT_REWORK_WEIGHT,
                 notify: Optional[Callable[[dict], None]] = None):
        if schedule.interval("disk") > 0 and disk_store is None:
            raise ValueError("disk tier scheduled but no disk_store given")
        if schedule.interval("partner") > 0 and partner_store is None:
            raise ValueError("partner tier scheduled but no partner_store")
        self.schedule = schedule
        self.device = DeviceRing(device_slots) \
            if schedule.interval("device") > 0 else None
        self.host = HostRing(host_slots) \
            if schedule.interval("host") > 0 else None
        self.disk = disk_store
        self.partner = partner_store
        self.restore_costs = dict(DEFAULT_RESTORE_COSTS)
        if restore_costs:
            self.restore_costs.update(restore_costs)
        self.rework_weight = float(rework_weight)
        self.notify = notify or (lambda e: None)
        self.events: List[Dict[str, Any]] = []
        self.saves_by_tier: Dict[str, int] = {}
        self.restores_by_tier: Dict[str, int] = {}

    # -- cadence ---------------------------------------------------------------

    def due(self, step: int) -> bool:
        return any(self.schedule.tier_due(t, step)
                   for t in self.schedule.enabled())

    def sync_due(self, step: int) -> bool:
        """True when a tier that pays a D2H transfer is due (host/disk/
        partner) — the engine forces a deferred-ring flush first so every
        durable version predates every unvalidated step."""
        return any(self.schedule.tier_due(t, step)
                   for t in ("host", "disk", "partner"))

    def fp_needed(self, step: int) -> bool:
        """Whether the engine should pay the state-fingerprint readback for
        this save: only the serialized tiers record it in a manifest."""
        return any(self.schedule.tier_due(t, step)
                   for t in ("disk", "partner"))

    # -- save ------------------------------------------------------------------

    def save(self, step: int, state, *, fingerprint=None,
             valid: Optional[bool] = None, kind: str = "system",
             async_: bool = True, keep_floor: Optional[int] = None,
             force: bool = False) -> List[str]:
        """Route one version into every due tier. Returns the tiers saved.

        One batched D2H transfer feeds host + disk + partner together;
        the device tier never leaves the accelerator. `force=True` hits
        every enabled tier regardless of cadence (the L3 validated-
        checkpoint boundary replicates into all tiers at once)."""
        saved: List[str] = []

        def _due(tier: str) -> bool:
            iv = self.schedule.interval(tier)
            return iv > 0 and (force or self.schedule.tier_due(tier, step))

        if self.device is not None and _due("device"):
            with obs.span("checkpoint_tier", tier="device", step=step):
                self.device.save(step, state, keep_floor)
            saved.append("device")

        host_due = self.host is not None and _due("host")
        disk_due = self.disk is not None and _due("disk")
        partner_due = self.partner is not None and _due("partner")
        if host_due or disk_due or partner_due:
            from repro.core import hostsync   # lazy: see store.py note
            leaves, treedef = jax.tree_util.tree_flatten(state)
            host_leaves = hostsync.batched_get(leaves,
                                               label="checkpoint_save")
            if host_due:
                with obs.span("checkpoint_tier", tier="host", step=step):
                    self.host.save(step, host_leaves, treedef, keep_floor)
                saved.append("host")
            if disk_due:
                with obs.span("checkpoint_tier", tier="disk", step=step):
                    self.disk.save(step, state, kind=kind, valid=valid,
                                   fingerprint=fingerprint, async_=async_,
                                   host_leaves=host_leaves)
                saved.append("disk")
            if partner_due:
                # independent manifest + digests: partner._write recomputes
                # them from the same host buffers
                with obs.span("checkpoint_tier", tier="partner", step=step):
                    self.partner.save(step, state, kind=kind, valid=valid,
                                      fingerprint=fingerprint, async_=async_,
                                      host_leaves=host_leaves)
                saved.append("partner")
        for t in saved:
            self.saves_by_tier[t] = self.saves_by_tier.get(t, 0) + 1
            obs.note_tier_save(t, step)
        return saved

    # -- version queries -------------------------------------------------------

    def _tier_versions(self, tier: str) -> List[int]:
        obj = getattr(self, tier, None)
        if obj is None:
            return []
        if tier in ("device", "host"):
            return obj.versions()
        return obj.steps()

    def versions(self) -> List[int]:
        out = set()
        for t in TIER_ORDER:
            out.update(self._tier_versions(t))
        return sorted(out)

    def tiers_with(self, version: int) -> List[str]:
        return [t for t in TIER_ORDER if version in self._tier_versions(t)]

    def latest_valid(self) -> Optional[int]:
        """Newest validated version across tiers (L3). Ring tiers only ever
        receive validated states under L3, so their slots count; disk
        tiers consult the manifest's valid flag."""
        cands: List[int] = []
        for t in ("device", "host"):
            cands.extend(self._tier_versions(t))
        for store in (self.disk, self.partner):
            if store is not None:
                v = store.latest(valid_only=True)
                if v is not None:
                    cands.append(v)
        return max(cands) if cands else None

    # -- restore planner -------------------------------------------------------

    def plan(self, version: Optional[int] = None,
             max_step: Optional[int] = None) -> List[Tuple[str, int]]:
        """Ordered restore candidates, cheapest first.

        With `version`: every tier holding exactly that version (tier cost
        order), then — as corruption fallbacks — every (tier, older
        version) candidate ranked by `restore_cost + rework_weight *
        (version - v)`. With only `max_step`: the full cost-ranked list of
        candidates at-or-below the bound (L3 restore, generic callers)."""
        ref = version if version is not None else max_step

        def cost(tier: str, v: int) -> float:
            c = self.restore_costs.get(tier, max(self.restore_costs.values()))
            if ref is not None:
                c += self.rework_weight * max(ref - v, 0)
            return c

        exact: List[Tuple[str, int]] = []
        older: List[Tuple[str, int]] = []
        for t in TIER_ORDER:
            for v in self._tier_versions(t):
                if max_step is not None and v > max_step:
                    continue
                if version is not None:
                    if v == version:
                        exact.append((t, v))
                    elif v < version:
                        older.append((t, v))
                else:
                    older.append((t, v))
        exact.sort(key=lambda tv: cost(*tv))
        older.sort(key=lambda tv: cost(*tv))
        return exact + older

    def _restore_from(self, tier: str, version: int, template):
        if tier == "device":
            return self.device.restore(version)
        if tier == "host":
            return self.host.restore(version, template)
        store = self.disk if tier == "disk" else self.partner
        return store.restore(version, template)

    def restore(self, version: Optional[int], template, *,
                max_step: Optional[int] = None
                ) -> Tuple[Any, Dict[str, Any]]:
        """Restore `version` (or the planner's best candidate <= `max_step`
        when version is None) from the cheapest tier holding it.

        A tier that fails — `CheckpointCorruptionError` from a digest
        mismatch, or a structurally unusable payload — is recorded as a
        `tier_fallback` event and the next candidate is tried; the caller
        sees a recovery event, not an exception, unless EVERY candidate is
        exhausted. Returns (state, info) where info carries the winning
        tier/version plus any fallbacks for the engine's recovery record."""
        with obs.span("restore_plan", version=version, max_step=max_step):
            candidates = self.plan(version=version, max_step=max_step)
        if not candidates:
            raise KeyError(
                f"no restorable version (requested {version}, "
                f"max_step {max_step})")
        fallbacks: List[Dict[str, Any]] = []
        last_err: Optional[Exception] = None
        for tier, v in candidates:
            try:
                with obs.span("restore", tier=tier, version=v):
                    state = self._restore_from(tier, v, template)
            except (CheckpointCorruptionError, FileNotFoundError, KeyError,
                    ValueError, OSError) as e:
                ev = {"kind": "tier_fallback", "tier": tier, "version": v,
                      "error": f"{type(e).__name__}: {e}"}
                fallbacks.append(ev)
                self.events.append(ev)
                obs.note_tier_event(ev)
                self.notify(ev)
                last_err = e
                continue
            self.restores_by_tier[tier] = \
                self.restores_by_tier.get(tier, 0) + 1
            obs.note_tier_restore(tier, v)
            info: Dict[str, Any] = {"tier": tier, "version": v}
            if fallbacks:
                info["fallbacks"] = fallbacks
            return state, info
        raise CheckpointCorruptionError(
            f"every tier failed restoring version {version}: "
            f"{fallbacks}") from last_err

    # -- retention -------------------------------------------------------------

    def keep_only(self, step: int) -> None:
        """L3's 'exactly one valid checkpoint' — enforced PER TIER."""
        for ring in (self.device, self.host):
            if ring is not None:
                ring.keep_only(step)
        for store in (self.disk, self.partner):
            if store is not None:
                store.delete_others_than(step)

    def gc_keep_last(self, n: int, keep_floor: Optional[int] = None) -> None:
        """Bounded-chain GC for the durable tiers (rings self-bound)."""
        for store in (self.disk, self.partner):
            if store is not None:
                store.gc_keep_last(n, keep_floor=keep_floor)

    def wait(self) -> None:
        """Durability barrier across every disk-backed tier."""
        for store in (self.disk, self.partner):
            if store is not None:
                store.wait()

    def drop_volatile(self) -> None:
        """Node loss (DESIGN.md §16): the device and host rings live in the
        failed topology's memory and do not survive a remesh — drop them so
        the restore planner can only be served by the durable tiers (disk /
        partner). The durable stores are untouched."""
        for ring in (self.device, self.host):
            if ring is not None:
                ring.clear()

    def clear(self) -> None:
        for ring in (self.device, self.host):
            if ring is not None:
                ring.clear()
        for store in (self.disk, self.partner):
            if store is not None:
                store.clear()


# ---------------------------------------------------------------------------
# Config-driven construction (the make_recovery entry point)
# ---------------------------------------------------------------------------

def parse_tiers(spec: str) -> Tuple[str, ...]:
    names = tuple(t.strip() for t in str(spec).split(",") if t.strip())
    bad = [t for t in names if t not in TIER_ORDER]
    if bad:
        raise ValueError(f"unknown checkpoint tier(s) {bad}; "
                         f"valid: {TIER_ORDER}")
    return names or ("disk",)


def make_tiered(sedar_cfg, directory: str,
                disk_store: Optional[CheckpointStore] = None,
                notify: Optional[Callable[[dict], None]] = None
                ) -> Optional[TieredCheckpointer]:
    """Build a `TieredCheckpointer` from a SedarConfig, or None when the
    config names only the classic flat disk store (backward compatible).

    Cadences: device defaults to EVERY step (`device_ckpt_interval`), host
    and partner default to the disk cadence (`checkpoint_interval`); the
    partner directory sits next to the primary with its own manifests."""
    import os

    names = parse_tiers(getattr(sedar_cfg, "ckpt_tiers", "disk"))
    if names == ("disk",):
        return None
    iv = int(sedar_cfg.checkpoint_interval)
    sched = TierSchedule(
        device=(int(getattr(sedar_cfg, "device_ckpt_interval", 1)) or 1)
        if "device" in names else 0,
        host=(int(getattr(sedar_cfg, "host_ckpt_interval", 0)) or iv)
        if "host" in names else 0,
        disk=iv if "disk" in names else 0,
        partner=(int(getattr(sedar_cfg, "partner_ckpt_interval", 0)) or iv)
        if "partner" in names else 0)
    partner_store = None
    if "partner" in names:
        partner_store = CheckpointStore(
            os.path.join(directory, "checkpoints_partner"),
            compress=bool(getattr(sedar_cfg, "ckpt_compress", False)))
    return TieredCheckpointer(
        sched,
        device_slots=int(getattr(sedar_cfg, "device_ring_slots", 4)),
        host_slots=int(getattr(sedar_cfg, "host_ring_slots", 4)),
        disk_store=disk_store if "disk" in names else None,
        partner_store=partner_store, notify=notify)
