"""SEDAR training runtime — a thin driver over the unified engine.

All detection/recovery protocol (replica comparison, TDC commit gate, FSC
validation, TOE watchdog, checkpoint boundaries, L1/L2/L3 + NMR recovery)
lives in `repro.core.engine.SedarEngine`; this module only supplies the
training-specific pieces:

  * the jit'd replica step (grads -> [inject] -> update fingerprint ->
    optimizer commit candidate),
  * state fingerprints (per-leaf for reports/localization; fused whole-state
    for the hot comparison path when SedarConfig.fused_fingerprint),
  * the pod/vote shard_map step for space redundancy, and
  * the outer loop (data, loss bookkeeping, wall budget).

Execution backends (SedarConfig.replication): "none", "sequential", "pod",
"vote", "abft", "hybrid" — see core/engine.py, abft/executor.py and
DESIGN.md §4/§10 for their semantics. The replica-free abft/hybrid backends
run this driver unchanged (single state image; detection comes from
checksummed kernels in the step — when instrumented — plus hybrid's
commit-time fingerprint validation).
"""
from __future__ import annotations

import dataclasses
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import RunConfig
from repro.core import hostsync
from repro.core.detection import (DetectionEvent, SedarSafeStop, Watchdog,
                                  make_pod_comparator, make_pod_injector)
from repro.core.engine import SedarEngine
from repro.core.fingerprint import (pytree_fingerprint,
                                    pytree_fingerprint_fused)
from repro.core.injection import InjectionFlag, InjectionSpec, inject_tree
from repro.core.policy import make_engine
from repro.core.recovery import make_recovery
from repro.data import make_pipeline
from repro.models import build_model
from repro.optim import apply_updates, make_optimizer


@dataclass
class TrainReport:
    steps_completed: int = 0
    losses: List[float] = field(default_factory=list)
    detections: List[DetectionEvent] = field(default_factory=list)
    recoveries: List[Dict[str, Any]] = field(default_factory=list)
    checkpoints: List[int] = field(default_factory=list)
    stopped: bool = False
    wall_s: float = 0.0
    final_state_fp: Optional[np.ndarray] = None
    # which checkpoint tier each rollback restore was served from
    # (DESIGN.md §12; empty for flat-disk configs or runs without recovery)
    restored_from: List[str] = field(default_factory=list)

    def summary(self) -> str:
        tiers = f" restored_from={self.restored_from}" \
            if self.restored_from else ""
        return (f"steps={self.steps_completed} detections={len(self.detections)} "
                f"recoveries={len(self.recoveries)} ckpts={len(self.checkpoints)} "
                f"stopped={self.stopped} wall={self.wall_s:.1f}s "
                f"loss={self.losses[-1] if self.losses else float('nan'):.4f}"
                f"{tiers}")


class SedarTrainer:
    """Drives SEDAR-protected training of any registered architecture."""

    def __init__(self, run_cfg: RunConfig, workdir: str,
                 mesh=None, rules=None,
                 inj_spec: Optional[InjectionSpec] = None,
                 toe_delay: Optional[Dict[str, Any]] = None,
                 data=None, notify: Optional[Callable] = None,
                 hosts_per_data_shard: int = 1,
                 autotune=None):
        self.cfg = run_cfg
        self.workdir = workdir
        # closed-loop knob tuning (DESIGN.md §17): a policy.Autotuner whose
        # maybe_tune() ticks after every committed step
        self.autotune = autotune
        os.makedirs(workdir, exist_ok=True)
        self.model = build_model(run_cfg.model)
        self.opt = make_optimizer(run_cfg.train)
        self.mesh = mesh
        self.rules = rules
        self.backend = run_cfg.sedar.replication
        self.inj_spec = inj_spec
        self.inj_flag = InjectionFlag(os.path.join(workdir, "injected.json"))
        self.toe_delay = toe_delay or {}
        self.hosts_per_data_shard = max(int(hosts_per_data_shard), 1)
        self.data = data or make_pipeline(run_cfg.model,
                                          run_cfg.train.global_batch,
                                          run_cfg.train.seq_len,
                                          run_cfg.train.seed)
        sedar = dataclasses.replace(run_cfg.sedar,
                                    checkpoint_dir=os.path.join(workdir, "ckpt"))
        self.sedar = sedar
        self.recovery = make_recovery(sedar, workdir)
        self.watchdog = Watchdog(sedar.toe_timeout_s)
        self.notify = notify or (lambda e: print(str(e), flush=True))
        self._build_step_fns()
        self.engine: SedarEngine = make_engine(
            sedar, backend=self.backend,
            step_fn=self._replica_step, state_fp_fn=self._state_fp,
            fast_state_fp_fn=self._state_fp_fast,
            pod_step=getattr(self, "_pod_step", None),
            pod_validate=getattr(self, "_pod_validate", None),
            pod_broadcaster=getattr(self, "_pod_bcast", None),
            n_replicas=(self.mesh.shape[sedar.replica_axis]
                        if self.backend in ("pod", "vote") else 2),
            lane_hosts=getattr(self, "_lane_hosts", None),
            recovery=self.recovery, watchdog=self.watchdog,
            inj_spec=inj_spec, inj_flag=self.inj_flag,
            init_fn=self.init_dual, notify=self.notify,
            delay_source=lambda: self.toe_delay,
            donate=run_cfg.train.donate_state)

    # -- state ---------------------------------------------------------------

    def init_state(self, seed: Optional[int] = None):
        key = jax.random.PRNGKey(self.cfg.train.seed if seed is None else seed)
        params = self.model.init(key)
        return {"params": params, "opt": self.opt.init(params),
                "step": jnp.zeros((), jnp.int32)}

    def init_dual(self, seed: Optional[int] = None):
        # the executor owns the dual representation ({"r0","r1"} images,
        # {"s"} stacked, {"r0"} per-pod) and any baseline state it keeps
        # (e.g. the hybrid fingerprint baseline on restart-from-scratch)
        return self.engine.executor.init_dual(self.init_state(seed))

    # -- jitted step functions ------------------------------------------------

    def _build_step_fns(self):
        model, opt = self.model, self.opt
        spec = self.inj_spec
        fused = bool(self.sedar.fused_fingerprint)

        def grad_fp(grads):
            # fused: ONE whole-state pass over the packed update buffer
            # (compare == "full" degenerates to the same fingerprint — the
            # hash covers every bit either way)
            if fused:
                return pytree_fingerprint_fused(grads)
            return pytree_fingerprint(grads)

        def replica_step(state, batch, replica_id, armed):
            def loss_fn(p):
                return model.loss(p, batch)[0]

            loss, grads = jax.value_and_grad(loss_fn)(state["params"])
            if spec is not None and spec.target == "grads":
                grads = inject_tree(grads, spec, step=state["step"],
                                    replica_id=replica_id, armed=armed)
            fp = grad_fp(grads)
            updates, new_opt = opt.update(grads, state["opt"],
                                          state["params"], state["step"])
            new_params = apply_updates(state["params"], updates)
            if spec is not None and spec.target == "params":
                new_params = inject_tree(new_params, spec, step=state["step"],
                                         replica_id=replica_id, armed=armed)
            if spec is not None and spec.target == "opt_state":
                new_opt = inject_tree(new_opt, spec, step=state["step"],
                                      replica_id=replica_id, armed=armed)
            cand = {"params": new_params, "opt": new_opt,
                    "step": state["step"] + 1}
            return cand, fp, loss

        def state_fp(state):
            return pytree_fingerprint({"params": state["params"],
                                       "opt": state["opt"]})

        def state_fp_fast(state):
            tree = {"params": state["params"], "opt": state["opt"]}
            if fused:
                return pytree_fingerprint_fused(tree)
            return pytree_fingerprint(tree)

        self._replica_step = jax.jit(replica_step)
        self._state_fp = jax.jit(state_fp)          # per-leaf: reports
        self._state_fp_fast = jax.jit(state_fp_fast)  # hot validation path

        if self.backend in ("pod", "vote"):
            assert self.mesh is not None, "pod backend requires a mesh"
            self._pod_cmp = make_pod_comparator(self.mesh,
                                                self.sedar.replica_axis)
            if self.backend == "vote":
                from repro.core.detection import make_pod_broadcaster
                self._pod_bcast = make_pod_broadcaster(
                    self.mesh, self.sedar.replica_axis)
            self._pod_inject = (make_pod_injector(self.mesh, spec,
                                                  self.sedar.replica_axis)
                                if spec is not None else None)

            # per-shard fingerprint lanes (DESIGN.md §16): one lane per data
            # shard so a divergence localizes to a device/host. Compare is a
            # pmax/pmin reduction over the replica axis — never a gather,
            # never a host readback on the hot path. The vote backend keeps
            # the legacy whole-state gather (its majority vote consumes
            # fp_all immediately).
            lanes = (dict(self.mesh.shape).get("data", 1)
                     if self.backend == "pod" else 0)
            self._n_lanes = lanes
            if lanes:
                from repro.core.detection import make_lane_comparator
                from repro.core.fingerprint import \
                    pytree_fingerprint_lanes as fp_lanes_fn
                self._lane_cmp = make_lane_comparator(
                    self.mesh, self.sedar.replica_axis)
                hpds = self.hosts_per_data_shard

                def _lane_hosts(lane_ids):
                    from repro.runtime.cluster import lanes_to_hosts
                    return lanes_to_hosts(lane_ids, hosts_per_data_shard=hpds)

                self._lane_hosts = _lane_hosts

            def pod_step(state, batch, armed):
                def loss_fn(p):
                    return model.loss(p, batch)[0]

                loss, grads = jax.value_and_grad(loss_fn)(state["params"])
                if self._pod_inject is not None and spec.target == "grads":
                    grads = jax.lax.cond(
                        armed,
                        lambda g: self._pod_inject(g, state["step"]),
                        lambda g: g, grads)
                if lanes:
                    eq = self._lane_cmp(fp_lanes_fn(grads, lanes))   # (L,)
                    ok = jnp.all(eq)
                    fp_all = None
                else:
                    fp = grad_fp(grads)
                    eq, fp_all = self._pod_cmp(fp)
                    ok = eq
                updates, new_opt = opt.update(grads, state["opt"],
                                              state["params"], state["step"])
                new_params = apply_updates(state["params"], updates)
                if self._pod_inject is not None and spec.target == "params":
                    new_params = jax.lax.cond(
                        armed,
                        lambda p: self._pod_inject(p, state["step"]),
                        lambda p: p, new_params)
                cand = {"params": new_params, "opt": new_opt,
                        "step": state["step"] + 1}
                new_state = jax.tree.map(lambda a, b: jnp.where(ok, a, b),
                                         cand, state)
                return new_state, eq, fp_all, loss

            def pod_validate(state):
                if lanes:
                    fpl = fp_lanes_fn({"params": state["params"],
                                       "opt": state["opt"]}, lanes)
                    # gather kept for the event detail (fault path only —
                    # pod_validate runs at validate/checkpoint boundaries,
                    # not per step)
                    _, fp_all = self._pod_cmp(fpl)
                    return self._lane_cmp(fpl), fp_all
                return self._pod_cmp(state_fp_fast(state))

            self._pod_step = jax.jit(pod_step)
            self._pod_validate = jax.jit(pod_validate)

    # -- driver ---------------------------------------------------------------

    def _host_step(self, dual) -> int:
        """ONE readback of the authoritative (device) step counter — paid at
        run start and after recoveries, never in the fault-free loop."""
        return hostsync.read_int(self.engine.executor.peek(dual, "step"),
                                 label="step_counter")

    def run(self, num_steps: int, dual=None, max_wall_steps: Optional[int] = None
            ) -> "tuple[dict, TrainReport]":
        """The zero-sync outer loop (DESIGN.md §11): the step counter is
        tracked host-side (committed outcomes advance it; recoveries resync
        it from the device once), per-step losses stay on device in
        `aux_buf` and drain in one batched transfer at the end — a
        fault-free protected step performs no device->host readback."""
        rep = TrainReport()
        t0 = time.time()
        eng = self.engine
        eng.reset()
        dual = dual or self.init_dual()
        budget = max_wall_steps or (6 * num_steps + 60)
        executed = 0
        step = self._host_step(dual)
        step0 = step
        # Loss bookkeeping: one device scalar per committed step, drained in
        # batched transfers (never one sync per step). `drained` holds the
        # host floats already fetched; the invariant `len(drained) +
        # len(aux_buf) == step - step0` lets a rollback truncate the record
        # so rep.losses matches the DELIVERED trajectory (the replay
        # re-records the window) instead of keeping corrupted-window losses.
        drained: List[float] = []
        aux_buf: List[Any] = []

        def drain():
            drained.extend(float(a) for a in
                           hostsync.batched_get(aux_buf, label="loss_drain"))
            aux_buf.clear()

        def truncate_to(n_keep: int):
            if n_keep <= len(drained):
                del drained[n_keep:]
                aux_buf.clear()
            else:
                del aux_buf[n_keep - len(drained):]

        while True:
            if step >= num_steps:
                # drain the deferred window before declaring completion: an
                # optimistic commit inside the last D steps may still fail
                event = eng.flush_deferred()
                if event is None:
                    break
                try:
                    dual = eng.on_detection(event, dual)
                except SedarSafeStop:
                    rep.stopped = True
                    break
                step = self._host_step(dual)
                truncate_to(step - step0)
                continue
            if executed >= budget:
                rep.stopped = True
                break
            executed += 1
            batch = {k: jnp.asarray(v) for k, v in
                     self.data.batch(step).items()}
            with obs.span("train_step", step=step):
                outcome = eng.run_protected_step(dual, batch, step)
            # unpacked and dropped: kept, the outcome would hold the
            # pre-recovery state on the device through the next step
            dual, aux, event = outcome.dual, outcome.aux, outcome.event
            committed = outcome.committed
            del outcome
            # aux is None when the executor refused the step before running
            # it (hybrid resident-state check) — there is no loss to record
            if committed and aux is not None:
                aux_buf.append(aux)
                step += 1
            if event is not None:
                try:
                    dual = eng.on_detection(event, dual)
                except SedarSafeStop:
                    rep.stopped = True
                    break
                # an ABFT forward correction COMMITS the (repaired) step:
                # keep the loss record aligned with committed steps
                if (eng.recoveries
                        and eng.recoveries[-1]["kind"] == "abft_correct"
                        and aux is not None):
                    aux_buf.append(aux)
                step = self._host_step(dual)
                truncate_to(step - step0)
            elif len(aux_buf) >= 4096 and not eng.pending_validation:
                # bound the live device buffers: piggyback one batched
                # fetch on a step whose window is already flushed (no
                # extra sync inside a deferred window)
                drain()
            if self.autotune is not None:
                # host-side only (registry/journal reads); lag changes land
                # via apply_reconfig and only at clean flush boundaries
                self.autotune.maybe_tune(eng, step)

        # final validation (paper: final results comparison)
        if not rep.stopped:
            event = eng.validate_final(dual, step)
            if event is not None:
                try:
                    dual = eng.on_detection(event, dual)
                except SedarSafeStop:
                    rep.stopped = True
        drain()
        rep.losses = drained
        rep.detections = list(eng.detections)
        rep.recoveries = list(eng.recoveries)
        rep.checkpoints = list(eng.checkpoints)
        rep.steps_completed = self._host_step(dual)
        rep.restored_from = [r["tier"] for r in rep.recoveries
                             if r.get("tier")]
        rep.final_state_fp = hostsync.read_scalar(
            self._state_fp(eng.executor.primary(dual)), label="final_fp")
        # durability barrier: async checkpoint writers are daemon threads —
        # without this, process exit can strand .tmp staging dirs and the
        # on-disk chain is shorter than rep.checkpoints claims. Tiered
        # configs barrier every disk-backed tier (primary AND partner).
        tiers = getattr(self.recovery, "tiers", None)
        if tiers is not None:
            tiers.wait()
        else:
            store = getattr(self.recovery, "store", None)
            if store is not None:
                store.wait()
        rep.wall_s = time.time() - t0
        return dual, rep
