"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 8 --level 3 [--smoke] \
        [--replication sequential|fused|pod|none] \
        [--inject-step N] [--manual-vote]

Without --smoke the architecture runs at its published widths (the chip's
path); --smoke runs the reduced per-arch config (CPU-runnable).
--replication pod places the two replicas on a ("pod", "data", "model") =
(2, n/2, 1) mesh of the n devices JAX finds.
--manual-vote runs the paper's BASELINE protocol: two independent instances,
final comparison, third run + majority vote on mismatch (Sec. 3, Eqs. 1-2).

--elastic drives the fail-in-place loop (DESIGN.md §16): an ElasticTrainer
run under a simulated cluster where one host can go dark mid-run and later
return — the run shrinks onto survivors from the last validated checkpoint,
then regrows and replays to a state bitwise-identical with an uninterrupted
run:

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
        --steps 12 --level 3 --elastic --n-hosts 2 \
        --lose-host 1 --lose-at 300 --return-at 700
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil

import numpy as np

from repro import obs
from repro.configs import (MeshConfig, RunConfig, SedarConfig, TrainConfig,
                           get_config, list_archs, reduce_for_smoke)
from repro.core.fingerprint import pytree_fingerprint
from repro.core.injection import InjectionSpec
from repro.core.policy import make_trainer
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_pod_mesh
from repro.runtime.cluster import Heartbeat


def manual_vote_baseline(run_cfg: RunConfig, workdir: str, steps: int,
                         inj_spec=None) -> None:
    """Paper baseline: two instances + compare; on mismatch, a third run and
    majority vote (semi-automatic, Eqs. 1-2)."""
    fps = []
    for inst in range(2):
        rc = dataclasses.replace(
            run_cfg, sedar=SedarConfig(level=1, replication="none"))
        tr = make_trainer(rc, f"{workdir}/inst{inst}",
                          inj_spec=inj_spec if inst == 1 else None)
        _, rep = tr.run(steps)
        fps.append(rep.final_state_fp[:, :2])
        print(f"[baseline] instance {inst}: {rep.summary()}")
    if np.array_equal(fps[0], fps[1]):
        print("[baseline] results MATCH — accepted")
        return
    print("[baseline] MISMATCH — launching third instance for majority vote")
    rc = dataclasses.replace(run_cfg,
                             sedar=SedarConfig(level=1, replication="none"))
    tr = make_trainer(rc, f"{workdir}/inst2")
    _, rep = tr.run(steps)
    third = rep.final_state_fp[:, :2]
    winner = 0 if np.array_equal(third, fps[0]) else 1
    print(f"[baseline] majority: instances {winner} and 2 agree -> "
          f"instance {1 - winner} was corrupted")


def run_elastic(run_cfg: RunConfig, args) -> None:
    """Fail-in-place demo loop (DESIGN.md §16). This process plays every
    host's heartbeat writer: each training segment advances a simulated
    clock 100 s and refreshes all heartbeats except the designated lost
    host during its dark window — the ClusterMonitor then sees a real
    stale-host and the ElasticTrainer shrinks/regrows exactly as it would
    under a genuine node loss."""
    from repro.runtime.elastic import ElasticTrainer

    hb_dir = os.path.join(args.workdir, "heartbeats")
    sim = {"now": 0.0}

    def write_beat(host: int, step: int) -> None:
        os.makedirs(hb_dir, exist_ok=True)
        with open(os.path.join(hb_dir, f"host_{host:05d}.json"), "w") as f:
            json.dump({"host": host, "step": int(step or 0),
                       "t": sim["now"]}, f)

    def tick(step) -> None:
        sim["now"] += 100.0
        for h in range(args.n_hosts):
            dark = (args.lose_host is not None and h == args.lose_host
                    and args.lose_at <= sim["now"] < args.return_at)
            if not dark:
                write_beat(h, step or 0)

    et = ElasticTrainer(run_cfg, args.workdir, n_hosts=args.n_hosts,
                        scan_interval=args.scan_interval,
                        clock=lambda: sim["now"], tick=tick)
    rep = et.run(args.steps)
    print(rep.summary())
    for r in rep.remeshes:
        print(f"  remesh[{r.phase}]: trigger step {r.trigger_step}, "
              f"restored step {r.restore_step} from tier "
              f"{r.restore_tier}, hosts {sorted(r.hosts)}, data "
              f"{r.old_data}->{r.new_data}, batch "
              f"{r.old_batch}->{r.new_batch}")
    for d in rep.decisions:
        print(f"  decision: {d.mode} (fail_in_place "
              f"{d.fail_in_place_hours:.3f} h vs restart "
              f"{d.restart_hours:.3f} h) — {d.notes}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=list_archs())
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--level", type=int, default=3, choices=(1, 2, 3))
    ap.add_argument("--replication", default="sequential",
                    choices=("none", "sequential", "fused", "pod"))
    ap.add_argument("--validate-lag", type=int, default=1,
                    help="deferred validation window D (DESIGN.md §11): "
                         "read commit predicates back every D steps")
    ap.add_argument("--ckpt-tiers", default="disk",
                    help="checkpoint tier hierarchy (DESIGN.md §12): comma-"
                         "list of device,host,disk,partner. device = on-"
                         "device snapshot ring (instant rollback, zero disk "
                         "reads), host = host-RAM ring, partner = redundant "
                         "second store (Tier-2 corruption fallback). "
                         "E.g. --ckpt-tiers device,host,disk")
    ap.add_argument("--ckpt-delta", action="store_true",
                    help="L2 delta checkpoints: leaves unchanged since the "
                         "previous version become manifest references "
                         "instead of re-serialized payloads")
    ap.add_argument("--ckpt-compress", action="store_true",
                    help="compress leaf payloads (np.savez_compressed); "
                         "bytes-on-disk reported in the manifest")
    ap.add_argument("--smoke", action="store_true",
                    help="run the reduced per-arch config (CPU-sized)")
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=16)
    ap.add_argument("--ckpt-interval", type=int, default=4)
    ap.add_argument("--workdir", default="out/sedar_train")
    ap.add_argument("--inject-step", type=int, default=None)
    ap.add_argument("--manual-vote", action="store_true")
    ap.add_argument("--host-id", type=int, default=0)
    # -- elastic fail-in-place (DESIGN.md §16) -------------------------------
    ap.add_argument("--elastic", action="store_true",
                    help="run under an ElasticTrainer: monitor heartbeats, "
                         "shrink onto survivors on node loss, regrow on "
                         "return (requires --level 3)")
    ap.add_argument("--n-hosts", type=int, default=2,
                    help="cluster width; the data axis gets one shard per "
                         "host in the demo mesh")
    ap.add_argument("--scan-interval", type=int, default=2,
                    help="steps per training segment between cluster scans")
    ap.add_argument("--lose-host", type=int, default=None,
                    help="simulate this host going dark (heartbeats stop)")
    ap.add_argument("--lose-at", type=float, default=300.0,
                    help="simulated-clock second the host goes dark "
                         "(the clock advances 100 s per segment)")
    ap.add_argument("--return-at", type=float, default=700.0,
                    help="simulated-clock second the host comes back")
    ap.add_argument("--metrics-dir", default=None,
                    help="enable the obs metrics registry + fault journal "
                         "(DESIGN.md §15): writes metrics.prom and "
                         "journal.jsonl here and prints the Prometheus "
                         "snapshot after the run")
    # -- closed-loop autotuning (DESIGN.md §17) ------------------------------
    ap.add_argument("--autotune", action="store_true",
                    help="closed-loop calibration: estimate t_step/t_sync/"
                         "MTBE online and retune the deferred-validation "
                         "lag + tier cadences at clean flush boundaries "
                         "(requires --metrics-dir for the estimator's "
                         "inputs)")
    ap.add_argument("--autotune-interval", type=int, default=16,
                    help="steps between autotuner evaluations")
    ap.add_argument("--slo-availability", type=float, default=None,
                    help="availability SLO target (e.g. 0.999); burn-rate "
                         "alerts fire when the error budget burns fast")
    ap.add_argument("--slo-goodput", type=float, default=None,
                    help="goodput SLO target as a 0-1 fraction of the "
                         "fault-free rate")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record per-stage trace spans to a Chrome-trace "
                         "JSON (open at ui.perfetto.dev)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    mesh = mesh_cfg = None
    if args.replication == "pod":
        mesh = make_pod_mesh()
        mesh_cfg = MeshConfig(shape=tuple(mesh.devices.shape),
                              axis_names=tuple(mesh.axis_names))
    if args.elastic:
        if args.level < 3:
            ap.error("--elastic requires --level 3 (a validated checkpoint "
                     "anchor is what makes shrink/regrow exact)")
        if args.global_batch % args.n_hosts:
            ap.error("--global-batch must divide evenly across --n-hosts")
        mesh_cfg = MeshConfig(shape=(args.n_hosts, 1),
                              axis_names=("data", "model"))
    rc = RunConfig(
        model=cfg,
        train=TrainConfig(global_batch=args.global_batch,
                          seq_len=args.seq_len, steps=args.steps,
                          warmup_steps=max(args.steps // 10, 1), lr=1e-3),
        mesh=mesh_cfg if mesh_cfg is not None else MeshConfig(),
        sedar=SedarConfig(level=args.level, replication=args.replication,
                          validate_lag=args.validate_lag,
                          checkpoint_interval=args.ckpt_interval,
                          param_validate_interval=args.ckpt_interval,
                          ckpt_tiers=args.ckpt_tiers,
                          ckpt_delta=args.ckpt_delta,
                          ckpt_compress=args.ckpt_compress))
    shutil.rmtree(args.workdir, ignore_errors=True)

    inj = None
    if args.inject_step is not None:
        inj = InjectionSpec(leaf_idx=3, flat_idx=11, bit=21,
                            step=args.inject_step, replica=1, target="grads")

    if args.manual_vote:
        manual_vote_baseline(rc, args.workdir, args.steps, inj)
        return

    ob = obs.configure(metrics_dir=args.metrics_dir, trace=args.trace)
    if args.elastic:
        run_elastic(rc, args)
        if args.metrics_dir:
            print(f"[obs] kpis: {ob.kpis(steps=args.steps)}")
        snap = ob.finalize()
        if snap:
            print(f"[obs] metrics snapshot "
                  f"({args.metrics_dir}/metrics.prom):")
            print(snap, end="")
        return
    tuner = None
    if args.autotune:
        from repro.core import temporal_model as tm
        from repro.core.policy import Autotuner, AutotuneConfig
        if not args.metrics_dir:
            ap.error("--autotune needs --metrics-dir (the estimator reads "
                     "the stage-duration histograms and the fault journal)")
        tuner = Autotuner(
            tm.PAPER_TABLE3["JACOBI"],
            AutotuneConfig(interval_steps=args.autotune_interval,
                           mode="train", backend=args.replication,
                           slo_availability=args.slo_availability,
                           slo_goodput=args.slo_goodput))
    hb = Heartbeat(os.path.join(args.workdir, "heartbeats"), args.host_id)
    with mesh if mesh is not None else contextlib.nullcontext():
        trainer = make_trainer(rc, args.workdir, mesh=mesh, inj_spec=inj,
                               autotune=tuner)
        dual, rep = trainer.run(args.steps)
    hb.beat(rep.steps_completed)
    print(rep.summary())
    for e in rep.detections:
        print(f"  detection: {e}")
    for r in rep.recoveries:
        print(f"  recovery: {r}")
    if args.metrics_dir:
        kpis = ob.kpis(steps=rep.steps_completed)
        print(f"[obs] kpis: {kpis}")
    if tuner is not None:
        snap = tuner.estimator.calibrated_params()
        print(f"[autotune] calibrated: t_step={snap.params.t_step:.3e} h, "
              f"t_sync={snap.params.t_sync:.3e} h, "
              f"mtbe={snap.mtbe_hours:.3g} h, "
              f"confidence={snap.confidence:.2f} "
              f"({snap.sample_counts})")
        print(f"[autotune] {len(tuner.alerts.records)} alert(s), "
              f"{tuner.evaluations} evaluation(s)")
    snap = ob.finalize()
    if snap:
        print(f"[obs] metrics snapshot ({args.metrics_dir}/metrics.prom):")
        print(snap, end="")
    if args.trace:
        print(f"[obs] trace written to {args.trace}")


if __name__ == "__main__":
    main()
