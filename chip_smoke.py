"""Bring-up check: protected serving and training of qwen2-0.5b on a TPU.

    python chip_smoke.py             # one chip: device, serve, model sanity, train
    python chip_smoke.py --chips 4   # four chips: pod-replicated training only

Everything runs in this one process, through the entry points a user calls
(`make_server(...).serve(...)`, `make_trainer(...).run(...)`), with random
weights made from `--seed`. The script exits non-zero and prints no result
line when JAX finds no TPU, when a phase raises, or when a check misses; it
never falls back to the CPU. Its last line on success is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The tokens/s, TTFT, compile seconds and peak bytes it prints are bring-up
output, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen2-0.5b"
SERVE_SLOTS = 4
SERVE_LAG = 8
SERVE_REQUESTS = 8
SERVE_PROMPTS = (64, 96, 128, 192, 256)
SERVE_MAX_NEW = (16, 24, 32)
SERVE_BUCKETS = (64, 128, 256)
SERVE_FAULT_TICK = 5
SERVE_FAULT_SLOT = 0           # the first request is admitted at tick 0
SANITY_PROMPT = 64
# max|logits_tpu - logits_cpu| / max|logits_cpu| with float32 compute and
# "highest" matmul precision on both sides
SANITY_TOL = 1e-3
# L2 keeps a device-ring copy of the whole stacked dual state beside the
# live one: 2 x (2 replicas x 12 B/param) plus the step's ~5 GB of
# temporaries (batch 4 x seq 512 logits over a 151,936 vocabulary) must fit
# 16 GB. Four layers of qwen2-0.5b widths do (~14.4 GB); eight do not.
TRAIN_LAYERS = 4
TRAIN_STEPS = 4
TRAIN_BATCH = 4
TRAIN_SEQ = 512
TRAIN_FAULT_STEP = 2
# per-step loss agreement of the pod run with the one-chip fused run
POD_LOSS_RTOL = 1e-2


class SmokeFailure(RuntimeError):
    """A check of this script missed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)
    print(f"  ok: {msg}", flush=True)


class CompileClock:
    """Seconds XLA spent compiling, from JAX's own compile-duration events."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.seconds = 0.0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        if event == self._event:
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def serve_requests(cfg, seed: int):
    from repro.runtime.scheduler import synthetic_requests
    return synthetic_requests(
        SERVE_REQUESTS, arrival_rate=0.5, prompt_lengths=SERVE_PROMPTS,
        max_new_choices=SERVE_MAX_NEW, vocab=cfg.vocab_size, seed=seed)


def phase_serve(cfg, params, seed: int, buckets=SERVE_BUCKETS):
    """Continuous-batching protected serving under `none`, `fused` and
    `fused` with a slot fault; returns the fused engine's validate HLO."""
    import jax
    from repro.configs import RunConfig, TrainConfig
    from repro.core.injection import InjectionSpec
    from repro.core.policy import make_server
    from repro.runtime.scheduler import stream_stats_ms

    template = serve_requests(cfg, seed)
    max_len = (max(r.prompt_len for r in template)
               + max(r.max_new_tokens for r in template) + 8)
    # the high exponent bit of a logit in the compute dtype
    fault = InjectionSpec(leaf_idx=SERVE_FAULT_SLOT, flat_idx=7,
                          bit=jax.numpy.finfo(cfg.dtype).bits - 2,
                          step=SERVE_FAULT_TICK, replica=1, target="slot")
    streams, reports, servers = {}, {}, {}
    for name, backend, spec in (("none", "none", None),
                                ("fused", "fused", None),
                                ("fused+fault", "fused", fault)):
        srv = make_server(RunConfig(model=cfg, train=TrainConfig()),
                          backend=backend, inj_spec=spec,
                          prefill_buckets=buckets, max_pack=4)
        t0 = time.time()
        n_prog = srv.warmup_prefill(params, max_len)
        print(f"  serve[{name}]: warmup compiled {n_prog} prefill programs "
              f"in {time.time() - t0:.1f}s", flush=True)
        runs = []
        for run in ("cold", "warm"):     # the cold run compiles the decode
            reqs = serve_requests(cfg, seed)
            out, rep = srv.serve(params, reqs, slots=SERVE_SLOTS,
                                 validate_lag=SERVE_LAG)
            ms = stream_stats_ms(out)
            runs.append({r.rid: list(r.tokens) for r in out})
            print(f"  serve[{name}] {run}: {len(rep.completed)}/{len(reqs)} "
                  f"done, {rep.tokens_emitted} tokens over {rep.steps} "
                  f"ticks in {rep.wall_s:.2f}s = {rep.tokens_per_s:.1f} "
                  f"tok/s, TTFT p50 {ms['ttft_p50_ms']:.1f} ms, detections="
                  f"{len(rep.detections)} retries={rep.retries} "
                  f"rollbacks={rep.rollbacks}", flush=True)
            check(sorted(rep.completed) == [r.rid for r in reqs]
                  and not rep.rejected and not rep.stopped,
                  f"serve[{name}] {run} completed every request")
            check(all(len(r.tokens) == r.max_new_tokens for r in out),
                  f"serve[{name}] {run} delivered each request's budget")
        check(runs[0] == runs[1],
              f"serve[{name}] cold and warm runs deliver the same streams")
        streams[name], reports[name], servers[name] = runs[1], rep, srv

    for name in ("none", "fused"):
        check(not reports[name].detections,
              f"clean serve[{name}] has 0 detections")
    rep = reports["fused+fault"]
    check(len(rep.detections) >= 1,
          f"fault run detected the slot fault ({len(rep.detections)})")
    check(rep.retries + rep.rollbacks >= 1,
          f"fault run recovered (retries={rep.retries}, "
          f"rollbacks={rep.rollbacks})")
    check(streams["fused+fault"] == streams["fused"],
          "fault run's delivered streams equal the clean fused run's, "
          "request by request")

    srv = servers["fused"]
    eng = srv._batch_engines[next(iter(srv._batch_engines))][0]
    state = jax.eval_shape(lambda: eng.executor.init_dual(
        srv.packed_state(SERVE_SLOTS, max_len)))
    return eng.executor._validate_jit.lower(state["s"]).compile().as_text()


def phase_sanity(cfg, params, seed: int, tol: float = SANITY_TOL):
    """Prefill logits of one prompt on the default device against the same
    jitted prefill on the in-process CPU device, float32 throughout."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import build_model

    model = build_model(dataclasses.replace(cfg, dtype="float32"))
    toks = jnp.asarray(np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (1, SANITY_PROMPT)), jnp.int32)
    prefill = jax.jit(lambda p, t: model.prefill(
        p, {"tokens": t}, SANITY_PROMPT + 8)[0])
    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(prefill(params, toks), np.float64)
        ref = np.asarray(prefill(jax.device_put(params, cpu),
                                 jax.device_put(toks, cpu)), np.float64)
    check(got.shape == ref.shape == (1, cfg.vocab_size)
          and np.isfinite(got).all(),
          f"prefill logits finite, shape {got.shape}")
    err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    print(f"  sanity: rel max-abs err {err:.3e} (tolerance {tol:g}), "
          f"argmax {int(got.argmax())} vs {int(ref.argmax())}", flush=True)
    check(err <= tol, f"logits agree with the CPU within {tol:g}")
    return err


def train_config(cfg, replication: str, layers: int = TRAIN_LAYERS,
                 batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ):
    from repro.configs import RunConfig, SedarConfig, TrainConfig
    return RunConfig(
        model=dataclasses.replace(cfg, num_layers=layers),
        train=TrainConfig(global_batch=batch, seq_len=seq,
                          steps=TRAIN_STEPS, warmup_steps=1, lr=1e-3),
        sedar=SedarConfig(level=2, replication=replication, validate_lag=1,
                          checkpoint_interval=2, ckpt_tiers="device,disk",
                          device_ring_slots=1))


def run_train(rc, workdir: str, mesh=None):
    """One protected training run with a grads fault on replica 1."""
    from repro.core.injection import InjectionSpec
    from repro.core.policy import make_trainer

    shutil.rmtree(workdir, ignore_errors=True)
    spec = InjectionSpec(leaf_idx=3, flat_idx=11, bit=30,
                         step=TRAIN_FAULT_STEP, replica=1, target="grads")
    tr = make_trainer(rc, workdir, mesh=mesh, inj_spec=spec)
    dual, rep = tr.run(TRAIN_STEPS)
    return tr, dual, rep


def check_train(name: str, rep) -> None:
    import numpy as np
    print(f"  train[{name}]: {rep.summary()}", flush=True)
    check([e.step for e in rep.detections] == [TRAIN_FAULT_STEP],
          f"train[{name}] fault detected at step {TRAIN_FAULT_STEP}")
    check(any(r["kind"] == "restore" for r in rep.recoveries),
          f"train[{name}] recovered ({[r['kind'] for r in rep.recoveries]}, "
          f"restored_from={rep.restored_from})")
    check(rep.steps_completed == TRAIN_STEPS and not rep.stopped
          and len(rep.losses) == TRAIN_STEPS
          and np.isfinite(rep.losses).all(),
          f"train[{name}] finished all {TRAIN_STEPS} steps, finite losses")


def phase_train(cfg, workdir: str, layers: int = TRAIN_LAYERS, **kw):
    rc = train_config(cfg, "fused", layers, **kw)
    print(f"  train: {cfg.name} widths with num_layers cut "
          f"{cfg.num_layers} -> {layers}; batch {rc.train.global_batch} x "
          f"seq {rc.train.seq_len}", flush=True)
    tr, dual, rep = run_train(rc, os.path.join(workdir, "fused"))
    check_train("fused", rep)
    hlo = tr.engine.executor._validate_jit.lower(dual["s"]).compile().as_text()
    del tr, dual
    gc.collect()     # trainer <-> engine callbacks form reference cycles
    shutil.rmtree(workdir, ignore_errors=True)
    return hlo


def phase_pod(cfg, workdir: str, layers: int = TRAIN_LAYERS, **kw):
    """`pod` replication on a ("pod", "data", "model") = (2, n/2, 1) mesh
    of every device against the one-chip `fused` run of the same steps."""
    import jax
    import numpy as np
    from repro.launch.mesh import make_pod_mesh

    devices = jax.devices()
    with jax.default_device(devices[0]):
        _, _, ref = run_train(train_config(cfg, "fused", layers, **kw),
                              os.path.join(workdir, "fused"))
    gc.collect()     # free the one-chip run before the pod run allocates
    check_train("fused, one chip", ref)
    mesh = make_pod_mesh(devices)
    print(f"  pod mesh {dict(mesh.shape)} over {len(devices)} devices",
          flush=True)
    with mesh:
        _, dual, rep = run_train(train_config(cfg, "pod", layers, **kw),
                                 os.path.join(workdir, "pod"), mesh=mesh)
    check_train("pod", rep)
    check([e.step for e in rep.detections] == [e.step for e in ref.detections],
          "pod detection falls at the same step as the one-chip run")
    rel = np.abs(np.asarray(rep.losses) - np.asarray(ref.losses)) \
        / np.abs(np.asarray(ref.losses))
    print(f"  losses pod {rep.losses}\n  losses fused {ref.losses}\n"
          f"  max rel diff {rel.max():.3e}", flush=True)
    check(rel.max() <= POD_LOSS_RTOL,
          f"per-step losses agree within rtol {POD_LOSS_RTOL:g}")
    held = {d.id: [0, 0] for d in devices}
    for leaf in jax.tree.leaves(dual):
        for sh in leaf.addressable_shards:
            held[sh.device.id][0] += 1
            held[sh.device.id][1] += sh.data.nbytes
    for d in devices:
        print(f"  device {d.id}: {held[d.id][0]} shards, "
              f"{held[d.id][1]} bytes of the pod state", flush=True)
    check(all(n > 0 for n, _ in held.values()),
          "every device holds shards of the pod state")
    shutil.rmtree(workdir, ignore_errors=True)
    return rep, ref


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the pod-replicated training phase "
                         "across four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", default=os.path.join(ROOT, "out",
                                                      "chip_smoke"))
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(jax.devices())}", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.kernels.fingerprint import default_interpret
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"== device: jax {jax.__version__}, {dev.device_kind} x "
          f"{len(jax.devices())}, compile cache {cache_dir}", flush=True)
    check(not default_interpret(), "Pallas kernels compile (no interpret)")
    cfg = get_config(ARCH)

    def phase(name, fn, *a, **kw):
        print(f"== {name}", flush=True)
        t0 = time.time()
        out = fn(*a, **kw)
        print(f"== {name}: {time.time() - t0:.1f}s wall, "
              f"{clock.lap():.1f}s compiling, peak_bytes_in_use "
              f"{peak_bytes(dev)}", flush=True)
        return out

    if args.chips == 4:
        phase("pod training, 4 chips", phase_pod, cfg, args.workdir)
    else:
        params = phase("init", lambda: jax.block_until_ready(jax.jit(
            build_model(cfg).init)(jax.random.PRNGKey(args.seed))))
        hlo = phase("serve", phase_serve, cfg, params, args.seed)
        check("tpu_custom_call" in hlo,
              "fused serve validate program holds the Pallas kernel")
        phase("model sanity", phase_sanity, cfg, params, args.seed)
        del params
        gc.collect()
        hlo = phase("train", phase_train, cfg, args.workdir)
        check("tpu_custom_call" in hlo,
              "fused train validate program holds the Pallas kernel")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
