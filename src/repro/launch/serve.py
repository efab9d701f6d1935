"""Serving launcher: synchronous batch or continuous-batching traffic replay.

Without --smoke the architecture runs at its published widths (the chip's
path); --smoke runs the reduced per-arch config (CPU-runnable).

Synchronous whole-batch decode (the original loop):

    PYTHONPATH=src python -m repro.launch.serve --arch xlstm-125m --smoke \
        --batch 4 --steps 16 [--dual]

Continuous-batching protected serving (DESIGN.md §13) replays an open-loop
synthetic traffic trace — arrival rate, prompt-length mix, per-request
token budgets — through the slot scheduler, optionally with a fault
campaign injected into the decode stream:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
        --continuous --requests 16 --slots 4 --arrival-rate 0.5 \
        --prompt-mix 4:0.5,8:0.3,16:0.2 --max-new 4,12 \
        --validate-lag 8 --backend sequential \
        --fault-slot 1 --fault-step 5

    # per-request rejection demo: a stuck bit on one slot
    ... --fault-slot 1 --fault-step 5 --fault-persistent --max-retries 3

    # bucketed packed prefill with AOT warmup (DESIGN.md §14): every
    # (bucket, pack) prefill program compiles BEFORE traffic — admission
    # then never pays a traffic-time compile
    ... --continuous --warmup --prefill-buckets 8,16,32 --max-pack 4
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import (RunConfig, TrainConfig, get_config, list_archs,
                           reduce_for_smoke)
from repro.core.policy import make_server
from repro.launch.compile_cache import enable_compile_cache


def _parse_prompt_mix(spec: str):
    """'4:0.5,8:0.5' -> (lengths, weights)."""
    lengths, weights = [], []
    for part in spec.split(","):
        length, _, w = part.partition(":")
        lengths.append(int(length))
        weights.append(float(w) if w else 1.0)
    return tuple(lengths), tuple(weights)


def _continuous(args, cfg, ob=None) -> None:
    from repro.core.injection import InjectionSpec
    from repro.runtime.scheduler import stream_stats_ms, synthetic_requests

    spec = None
    if args.fault_slot is not None:
        if args.backend in ("abft", "hybrid"):
            # replica-free backends execute ONE instance (replica_id 0) and
            # a pre-encode logits flip is invisible to the checksum guard by
            # construction — inject in the KERNEL domain instead (between
            # compute and verify, the fault class ABFT exists to catch),
            # into the chosen slot's row of the checksummed block
            spec = InjectionSpec(
                leaf_idx=0,
                flat_idx=args.fault_slot * (cfg.vocab_size + 1) + 7,
                bit=30, step=args.fault_step, replica=0, target="kernel",
                persistent=args.fault_persistent)
        else:
            # replica 0 for the unprotected baseline (there IS no replica
            # 1 — the corruption must land on the instance that runs, and
            # the stream visibly corrupts with nothing detecting it)
            replica = 0 if args.backend == "none" else 1
            # the high exponent bit of a logit in the compute dtype
            spec = InjectionSpec(
                leaf_idx=args.fault_slot, flat_idx=7,
                bit=jnp.finfo(cfg.dtype).bits - 2,
                step=args.fault_step, replica=replica, target="slot",
                persistent=args.fault_persistent)
    buckets = (tuple(int(b) for b in args.prefill_buckets.split(","))
               if args.prefill_buckets else None)
    srv = make_server(RunConfig(model=cfg, train=TrainConfig()),
                      dual=(args.backend == "sequential"),
                      backend=args.backend, inj_spec=spec,
                      max_retries=args.max_retries,
                      prefill_buckets=buckets, max_pack=args.max_pack)
    params = srv.model.init(jax.random.PRNGKey(0))
    lengths, weights = _parse_prompt_mix(args.prompt_mix)
    reqs = synthetic_requests(
        args.requests, arrival_rate=args.arrival_rate,
        prompt_lengths=lengths, length_weights=weights,
        max_new_choices=tuple(int(x) for x in args.max_new.split(",")),
        vocab=min(cfg.vocab_size, 200), seed=args.seed)
    if args.warmup:
        # same max_len formula serve() uses, so the warmed programs are the
        # ones traffic hits (DESIGN.md §14 AOT warmup contract)
        max_len = (max(r.prompt_len for r in reqs)
                   + max(r.max_new_tokens for r in reqs) + 8)
        n = srv.warmup_prefill(params, max_len)
        print(f"[SEDAR] prefill warmup: {n} (bucket, pack) programs "
              f"compiled ahead of traffic")
    tuner = None
    if args.autotune:
        from repro.core import temporal_model as tm
        from repro.core.policy import Autotuner, AutotuneConfig
        tuner = Autotuner(
            tm.PAPER_TABLE3["JACOBI"],
            AutotuneConfig(interval_steps=args.autotune_interval,
                           mode="serve", serve_slots=args.slots,
                           backend=args.backend,
                           slo_availability=args.slo_availability,
                           slo_goodput=args.slo_goodput))
    out, rep = srv.serve(
        params, reqs, slots=args.slots, validate_lag=args.validate_lag,
        queue_depth=args.queue_depth, autotune=tuner,
        notify_reject=lambda r, e: print(
            f"[SEDAR] request {r.rid} REJECTED after {e.boundary} fault "
            f"(per-request safe stop)", flush=True))
    ms = stream_stats_ms(out)
    print(f"{args.arch}: {rep.tokens_emitted} tokens delivered over "
          f"{rep.steps} protected steps ({rep.tokens_per_s:.1f} tok/s, "
          f"goodput {rep.goodput_tokens_per_step:.2f} tok/step), "
          f"p50/p99 inter-token {ms['itl_p50_ms']:.2f}/"
          f"{ms['itl_p99_ms']:.2f} ms, "
          f"p50/p99 TTFT {ms['ttft_p50_ms']:.2f}/{ms['ttft_p99_ms']:.2f} ms, "
          f"p50/p99 TTLT {ms['ttlt_p50_ms']:.2f}/{ms['ttlt_p99_ms']:.2f} ms")
    print(f"  completed={len(rep.completed)} rejected={rep.rejected} "
          f"detections={len(rep.detections)} retries={rep.retries} "
          f"rollbacks={rep.rollbacks} "
          f"truncated+redecoded={rep.truncated_tokens} tokens, "
          f"prefill packs={rep.prefill_packs} "
          f"prefill retries={rep.prefill_retries}")
    for e in rep.detections:
        print(f"  {e} slots={e.detail.get('slots')}")
    if ob is not None and ob.journal is not None:
        kpis = ob.kpis(steps=rep.steps, tokens=rep.tokens_emitted)
        print(f"[obs] kpis: {kpis}")
        rows = obs.reconcile_with_advice(kpis,
                                         validate_lag=args.validate_lag)
        for row in rows:
            print(f"[obs] predicted-vs-observed {row['metric']}: "
                  f"predicted {row['predicted']}, observed "
                  f"{row['observed']} -> {'OK' if row['ok'] else 'MISS'}")
    if tuner is not None:
        snap = tuner.estimator.calibrated_params()
        print(f"[autotune] calibrated: t_step={snap.params.t_step:.3e} h, "
              f"t_sync={snap.params.t_sync:.3e} h, "
              f"mtbe={snap.mtbe_hours:.3g} h, "
              f"confidence={snap.confidence:.2f}")
        print(f"[autotune] {len(tuner.alerts.records)} alert(s), "
              f"{tuner.evaluations} evaluation(s)")


def _sync(args, cfg) -> None:
    srv = make_server(RunConfig(model=cfg, train=TrainConfig()),
                      dual=args.dual)
    params = srv.model.init(jax.random.PRNGKey(0))
    prompts = {"tokens": jnp.asarray(
        np.random.RandomState(0).randint(0, min(cfg.vocab_size, 200),
                                         (args.batch, args.prompt_len)),
        jnp.int32)}
    if cfg.frontend:
        prompts["frontend_embeds"] = 0.1 * jnp.ones(
            (args.batch, cfg.frontend_seq, cfg.frontend_dim), jnp.float32)
    toks, rep = srv.generate(params, prompts, steps=args.steps)
    tps = rep.tokens_emitted / max(rep.wall_s, 1e-9)
    dev = jax.devices()[0]
    print(f"{args.arch}: {rep.tokens_emitted} tokens, {tps:.1f} tok/s "
          f"({dev.platform} {dev.device_kind}"
          f"{', smoke config' if args.smoke else ''}), "
          f"detections={len(rep.detections)}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m", choices=list_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--dual", action="store_true",
                    help="SEDAR dual-execution detection on decode")
    ap.add_argument("--smoke", action="store_true",
                    help="run the reduced per-arch config (CPU-sized)")
    # -- continuous-batching traffic replay (DESIGN.md §13) -----------------
    ap.add_argument("--continuous", action="store_true",
                    help="slot-scheduled continuous batching with "
                         "per-request recovery")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--arrival-rate", type=float, default=1.0,
                    help="open-loop arrivals per decode tick")
    ap.add_argument("--prompt-mix", default="4:0.5,8:0.5",
                    help="len:weight[,len:weight...] prompt-length mix")
    ap.add_argument("--max-new", default="4,12",
                    help="comma list of per-request token budgets")
    ap.add_argument("--queue-depth", type=int, default=0,
                    help="admission-queue bound (0 = unbounded); a full "
                         "queue sheds load (backpressure rejection)")
    ap.add_argument("--validate-lag", type=int, default=None,
                    help="deferred-validation window D (DESIGN.md §11/§13)")
    ap.add_argument("--backend", default="sequential",
                    choices=["none", "sequential", "fused", "abft",
                             "hybrid"])
    ap.add_argument("--max-retries", type=int, default=8,
                    help="consecutive per-slot failures before the request "
                         "is rejected (per-request L1)")
    ap.add_argument("--prefill-buckets", default="",
                    help="comma list of prompt-length buckets for packed "
                         "admission prefill (empty = geometric default, "
                         "DESIGN.md §14)")
    ap.add_argument("--max-pack", type=int, default=4,
                    help="max prompts packed into one prefill launch")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile every (bucket, pack) prefill program "
                         "before traffic (no traffic-time compiles)")
    ap.add_argument("--seed", type=int, default=0)
    # fault campaign
    ap.add_argument("--fault-slot", type=int, default=None,
                    help="inject a slot-localized SDC into this slot")
    ap.add_argument("--fault-step", type=int, default=5)
    ap.add_argument("--fault-persistent", action="store_true",
                    help="stuck bit: re-inject every step (drives the "
                         "per-request rejection path)")
    # -- cluster membership (DESIGN.md §16) ---------------------------------
    ap.add_argument("--heartbeat-dir", default=None,
                    help="publish this server's liveness to a shared "
                         "heartbeat directory and report any stale peers "
                         "after the run (a fleet supervisor uses the same "
                         "directory to drain a dead replica's traffic)")
    ap.add_argument("--host-id", type=int, default=0)
    ap.add_argument("--n-hosts", type=int, default=1)
    ap.add_argument("--hb-timeout", type=float, default=60.0,
                    help="seconds without a heartbeat before a peer is "
                         "declared stale")
    # -- observability (DESIGN.md §15) --------------------------------------
    ap.add_argument("--metrics-dir", default=None,
                    help="enable the obs metrics registry + fault journal: "
                         "writes metrics.prom and journal.jsonl here and "
                         "prints the Prometheus snapshot after the run")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record per-stage trace spans to a Chrome-trace "
                         "JSON (open at ui.perfetto.dev)")
    # -- closed-loop autotuning (DESIGN.md §17) ------------------------------
    ap.add_argument("--autotune", action="store_true",
                    help="closed-loop calibration: estimate decode-tick/"
                         "flush costs and MTBE online, retune the serve "
                         "lag at clean flush boundaries (needs "
                         "--metrics-dir + --continuous)")
    ap.add_argument("--autotune-interval", type=int, default=16,
                    help="decode ticks between autotuner evaluations")
    ap.add_argument("--slo-availability", type=float, default=None,
                    help="availability SLO target (e.g. 0.999)")
    ap.add_argument("--slo-goodput", type=float, default=None,
                    help="goodput SLO target as a 0-1 fraction")
    args = ap.parse_args()
    if args.autotune and not (args.continuous and args.metrics_dir):
        ap.error("--autotune needs --continuous and --metrics-dir")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    ob = obs.configure(metrics_dir=args.metrics_dir, trace=args.trace)
    hb = mon = None
    if args.heartbeat_dir:
        from repro.runtime.cluster import ClusterMonitor, Heartbeat
        hb = Heartbeat(args.heartbeat_dir, args.host_id)
        hb.beat(0)
        mon = ClusterMonitor(args.heartbeat_dir, args.n_hosts,
                             timeout_s=args.hb_timeout)
    if args.continuous:
        _continuous(args, cfg, ob)
    else:
        _sync(args, cfg)
    if hb is not None:
        if not hb.beat(args.steps):
            print(f"[cluster] heartbeat write failed "
                  f"({hb.io_errors} IO errors) — peers will see this "
                  f"host as stale")
        stale = mon.stale_hosts()
        print(f"[cluster] host {args.host_id} of {args.n_hosts}: "
              f"{'stale peers ' + str(stale) if stale else 'all peers live'}")
    snap = ob.finalize()
    if snap:
        print(f"[obs] metrics snapshot ({args.metrics_dir}/metrics.prom):")
        print(snap, end="")
    if args.trace:
        print(f"[obs] trace written to {args.trace}")


if __name__ == "__main__":
    main()
