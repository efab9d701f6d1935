"""Detection machinery: replica comparison at propagation boundaries + TOE
watchdog (paper Sec. 3.1).

Boundaries (DESIGN.md §2):
  * commit   -- gradient/update fingerprints compared every
                `validate_interval` steps BEFORE the optimizer commit
                (paper: message buffers compared before MPI_Send). TDC class.
  * validate -- full-state fingerprints compared every
                `param_validate_interval` steps and at end of run
                (paper: final-result comparison). FSC class.
  * toe      -- replica heartbeat timeout (paper: flow separation of the two
                replicas in a homogeneous dedicated system).

Two replica backends:
  * sequential: both replicas execute on the same devices one after the other
    (CPU tests, single-pod operation). Comparison is plain array equality.
  * pod: replicas live on different pods of the production mesh; fingerprints
    are exchanged with an all-gather over the replica axis inside shard_map
    (a few hundred bytes over ICI/DCN).
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.fingerprint import fingerprints_equal


@dataclass
class DetectionEvent:
    step: int
    boundary: str            # commit | validate | toe | final
    effect: str = ""         # TDC | FSC | TOE (classification, best effort)
    detail: Dict[str, Any] = field(default_factory=dict)

    def __str__(self):
        return (f"[SEDAR] fault detected at step {self.step} "
                f"(boundary={self.boundary}{', ' + self.effect if self.effect else ''})")


class SedarSafeStop(RuntimeError):
    """L1: notification + safe stop (paper Sec. 3.1)."""

    def __init__(self, event: DetectionEvent):
        super().__init__(str(event))
        self.event = event


# ---------------------------------------------------------------------------
# Pod-axis comparison (shard_map over the replica axis)
# ---------------------------------------------------------------------------

def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_pod_comparator(mesh, axis: str = "pod"):
    """Returns fn(fp) -> (all_equal: bool[], fp_all: (n_replicas, ...))

    `fp` is logically replicated but physically per-pod (it diverges only
    under a fault). The all-gather is explicit so XLA cannot fold it away."""

    def inner(fp):
        fp_all = jax.lax.all_gather(fp, axis)          # (n_pods, L, 4)
        eq = jnp.all(fp_all[..., :2] == fp_all[:1, ..., :2])
        return eq, fp_all

    return _shard_map(inner, mesh, in_specs=P(), out_specs=(P(), P()))


def make_lane_comparator(mesh, axis: str = "pod"):
    """Per-lane replica agreement via pure reductions (DESIGN.md §16).

    Takes lane fingerprints ``(L, 4) u32`` (logically replicated, physically
    per-pod) and returns ``eq_lanes: bool (L,)`` — lane i True iff every
    replica agrees on lane i's hash words. Implemented as pmax/pmin over the
    replica axis instead of an all-gather: the hot path moves O(L) words and
    never materializes the (n_replicas, L, 4) matrix; replicas agree exactly
    when max == min elementwise. No host readback — the caller parks or
    reduces the vector on device (§11 zero-sync contract)."""

    def inner(fp_lanes):
        h = fp_lanes[..., :2].astype(jnp.uint32)       # hash words only
        mx = jax.lax.pmax(h, axis)
        mn = jax.lax.pmin(h, axis)
        return jnp.all(mx == mn, axis=-1)              # (L,)

    return _shard_map(inner, mesh, in_specs=P(), out_specs=P())


def make_pod_broadcaster(mesh, axis: str = "pod"):
    """Beyond-paper N-modular redundancy: returns fn(state, src) that copies
    pod `src`'s physical state to every pod (collective-permute, memory-light)
    — forward correction after a majority vote, no rollback needed.
    `src` must be a static Python int (the runtime learns it from fp_all)."""
    n = mesh.shape[axis]

    def make(src: int):
        def inner(x):
            # one-to-many broadcast as a masked psum: only the src replica
            # contributes, so the sum is bitwise x_src on every pod
            me = jax.lax.axis_index(axis)
            if jnp.issubdtype(x.dtype, jnp.integer) or x.dtype == jnp.bool_:
                xi = x.astype(jnp.int32)
                out = jax.lax.psum(jnp.where(me == src, xi, 0), axis)
                return out.astype(x.dtype)
            contrib = jnp.where(me == src, x, jnp.zeros_like(x))
            return jax.lax.psum(contrib, axis)

        def bcast(tree):
            return jax.tree.map(
                lambda x: _shard_map(inner, mesh, in_specs=P(),
                                     out_specs=P())(x), tree)
        return bcast

    return make


def majority_replica(fp_all: "np.ndarray"):
    """Host-side majority vote over gathered fingerprints — (n_replicas, 4)
    for the fused whole-state hash, (n_replicas, L, 4) for per-leaf.

    Returns (src_replica, ok) — ok False when no strict majority exists."""
    import numpy as np
    fp_all = np.asarray(fp_all)
    n = fp_all.shape[0]
    keys = [fp_all[i].reshape(-1, 4)[:, :2].tobytes() for i in range(n)]
    best, count = None, 0
    for i, k in enumerate(keys):
        c = keys.count(k)
        if c > count:
            best, count = i, c
    return best, count > n // 2


def make_pod_injector(mesh, spec, axis: str = "pod"):
    """Returns fn(tree, step) that flips spec's bit on pod == spec.replica
    only (physical divergence of a logically-replicated tree)."""
    from repro.core.injection import flip_bit

    def apply(tree, step):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        x = leaves[spec.leaf_idx]

        def inner(xl, st):
            rid = jax.lax.axis_index(axis)
            fire = jnp.logical_and(rid == spec.replica, st == spec.step)
            return jnp.where(fire, flip_bit(xl, spec.flat_idx, spec.bit), xl)

        leaves[spec.leaf_idx] = _shard_map(
            inner, mesh, in_specs=(P(), P()), out_specs=P())(x, jnp.asarray(step))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return apply


# ---------------------------------------------------------------------------
# TOE watchdog (host-side heartbeats)
# ---------------------------------------------------------------------------

class Watchdog:
    """Per-replica heartbeat monitor. The runtime beats around every replica
    execution; `check()` flags replicas whose last beat is older than
    `timeout_s` — the paper's configurable-lapse TOE detector. A replica that
    never progresses (infinite loop) is definitely detected."""

    def __init__(self, timeout_s: float, n_replicas: int = 2):
        self.timeout_s = timeout_s
        self.last_beat: Dict[int, float] = {r: time.monotonic()
                                            for r in range(n_replicas)}
        self.step_time: Dict[int, float] = {}
        # per-replica wall-clock separation needs a device sync after each
        # replica launch; executors only pay it while the watchdog is armed
        # (scenario delays arm it implicitly; see SequentialExecutor)
        self.armed: bool = False

    def arm(self) -> None:
        self.armed = True

    def disarm(self) -> None:
        self.armed = False

    def beat(self, replica: int, step: int) -> None:
        now = time.monotonic()
        prev = self.last_beat.get(replica, now)
        self.last_beat[replica] = now
        self.step_time[replica] = now - prev

    def stale(self) -> List[int]:
        now = time.monotonic()
        return [r for r, t in self.last_beat.items()
                if now - t > self.timeout_s]

    def skew(self) -> float:
        """Max pairwise difference of last-beat times — replica flow
        separation (the paper's 'appreciable delay between the two replicas')."""
        ts = list(self.last_beat.values())
        return max(ts) - min(ts) if len(ts) > 1 else 0.0

    def check(self, step: int) -> Optional[DetectionEvent]:
        bad = self.stale()
        if bad:
            return DetectionEvent(step=step, boundary="toe", effect="TOE",
                                  detail={"stale_replicas": bad,
                                          "timeout_s": self.timeout_s})
        return None
