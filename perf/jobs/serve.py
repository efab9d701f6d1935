"""Serving job: protected continuous-batching decode through
`make_server(...).serve(...)`, the entry a user calls.

Set-up (counted in `setup_s`): weights made on the device from the seed;
the server; the packed prefill programs of every bucket the traffic uses
(AOT, no plain programs); one warm-up `serve()` call over the traffic's
own sizes with short budgets, which compiles the decode tick, the deferred
flush and the admission scatters.

Window: back-to-back `serve()` calls, each handed the traffic's backlog of
fresh prompts; it ends with the first call that ends after `--seconds`.
`serve_tokens_per_s` is the tokens of completed requests (each validated
before delivery) over the whole window; `tpot_p90_ms` is the nearest-rank
90th percentile over every completed request of (last token's stamp -
first token's stamp) / (tokens - 1).

Traced run (`--trace 1`): the program's host spans are on, and the
profiler traces the window's first call (device ops and annotations; the
Python tracer off), which the per-layer metrics read. The window ends
with that call: a traced run reports no end-to-end metric.

Correctness, after the window has closed, the peak memory has been read
and the server is gone: every request of the window completed with its
whole budget, and the served tokens of a sample drawn from the seed (the
request with the most tokens always in it) lie within the cell's limit of
the plain float32 reference's best logits (`check.py`). With a control
(`--control fp8`), the tokens that the float8 reference puts first at the
same positions take the served tokens' place in that comparison, which
has to fail it.

Notes on stderr, for finding what makes a window slow: each call's
length, the share of slot-ticks that decode (`slot occupancy`), the share
of ticks after the last admission (`tail`), the widest gap between two
token deliveries on the host and where in its call it fell, and the
window's garbage-collection pauses.
"""
from __future__ import annotations

import gc
import os
import shutil
import time
from types import SimpleNamespace
from typing import Any, Dict, List, Tuple

import numpy as np

from perf import check
from perf.compiles import CompileClock

CALL_ANNOTATION = "perf_serve_call"


def percentile(values, q: float) -> float:
    """Nearest rank: the ceil(q/100 * n)-th smallest value."""
    vals = sorted(values)
    rank = int(np.ceil(q / 100.0 * len(vals)))
    return float(vals[min(max(rank, 1), len(vals)) - 1])


class Server:
    """The program under test with the traffic's settings."""

    def __init__(self, model, traffic, params):
        from repro.configs import RunConfig, SedarConfig, TrainConfig
        from repro.core.policy import make_server
        self.traffic = traffic
        self.params = params
        self.lag = int(traffic["validate_lag"])
        rc = RunConfig(model=model, train=TrainConfig(),
                       sedar=SedarConfig(validate_lag=self.lag))
        self.srv = make_server(rc, backend=traffic["backend"],
                               prefill_buckets=traffic["buckets"],
                               max_pack=int(traffic["max_pack"]))

    def serve(self, reqs, max_len: int):
        return self.srv.serve(
            self.params, reqs, slots=int(self.traffic["slots"]),
            max_len=max_len, validate_lag=self.lag)


def _request_maker():
    from repro.runtime.scheduler import Request

    def make(rid, prompt, max_new):
        return Request(rid=rid, prompt=prompt, max_new_tokens=int(max_new))
    return make


class GcClock:
    """Pauses of Python's garbage collector while it is open."""

    def __init__(self):
        self.count, self.total_s, self.worst_s = 0, 0.0, 0.0
        self._start = None
        gc.callbacks.append(self._note)

    def _note(self, phase, _info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            d = time.perf_counter() - self._start
            self.count += 1
            self.total_s += d
            self.worst_s = max(self.worst_s, d)
            self._start = None

    def close(self):
        gc.callbacks.remove(self._note)


def call_stats(call, slots: int) -> Dict[str, float]:
    """Where one serve() call's time went, from the program's own counts
    and stamps: decode slot-ticks used, ticks after the last admission,
    and the widest gap between two token deliveries."""
    reqs, steps = call["reqs"], max(call["rep"].steps, 1)
    decoded = sum(max(len(r.tokens) - 1, 0) for r in reqs)
    last_admit = max((r.admit_step or 0) for r in reqs)
    stamps = sorted([call["wall"]] + [t for r in reqs for t in r.token_times])
    gaps = np.diff(stamps) if len(stamps) > 1 else np.zeros(1)
    at = int(np.argmax(gaps))
    return {"seconds": call["b"] - call["a"], "steps": steps,
            "occupancy_pct": 100.0 * decoded / (slots * steps),
            "tail_pct": 100.0 * max(steps - last_admit, 0) / steps,
            "widest_gap_s": float(gaps[at]),
            "widest_gap_at_s": stamps[at] - call["wall"]}


def _profile_window(pd, a_mono_ns: float):
    """The traced call's bounds on the trace's clock and the offset from
    the host's monotonic clock to it."""
    from perf.trace_reduce import host_events
    ev = host_events(pd, CALL_ANNOTATION)
    if not ev:
        raise RuntimeError(f"the trace holds no {CALL_ANNOTATION} span")
    start, end = ev[0]
    return start, end, start - a_mono_ns


def run(ctx) -> SimpleNamespace:
    import jax
    from repro import obs

    cfg, arch, traffic, gen = ctx.cfg, ctx.arch, ctx.traffic, ctx.gen
    vocab = int(cfg["vocab_size"])
    max_len = gen.max_len(traffic)
    mk = _request_maker()
    clock = CompileClock()
    notes: List[str] = []

    params = arch.weights.program_weights(cfg, ctx.seed)
    server = Server(arch.weights.model_config(cfg), traffic, params)
    n_prog = server.srv.warmup_prefill(params, max_len, plain_batches=())
    warm = gen.requests(traffic, vocab, ctx.seed, -1, mk)
    server.serve(warm, max_len)
    setup_s = time.monotonic() - ctx.t_start
    n_comp, s_comp = clock.lap()
    notes.append(f"setup: {setup_s:.3f} s, {n_prog} prefill programs, "
                 f"{n_comp} compiles ({s_comp:.1f} s)")

    rec = obs.enable_trace() if ctx.trace else None
    rec_mono0 = time.monotonic()
    calls: List[Dict[str, Any]] = []
    gc_clock = GcClock()
    t0 = time.monotonic()
    while True:
        reqs = gen.requests(traffic, vocab, ctx.seed, len(calls), mk)
        traced = ctx.trace and not calls
        if traced:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)
        a, wall = time.monotonic(), time.time()
        with jax.profiler.TraceAnnotation(CALL_ANNOTATION):
            out, rep = server.serve(reqs, max_len)
        b = time.monotonic()
        if traced:
            jax.profiler.stop_trace()
        calls.append({"reqs": out, "rep": rep, "a": a, "b": b,
                      "wall": wall})
        if traced or b - t0 >= ctx.seconds:
            break
    window_s = calls[-1]["b"] - t0
    gc_clock.close()
    n_comp, s_comp = clock.lap()
    memory_peak = _peak(ctx.devices)

    attempted = failed = tokens = 0
    tpot, done = [], []
    detections = 0
    for c in calls:
        detections += len(c["rep"].detections)
        for r in c["reqs"]:
            attempted += 1
            if r.status != "done" or len(r.tokens) != r.max_new_tokens:
                failed += 1
                continue
            tokens += len(r.tokens)
            done.append((np.asarray(r.prompt), list(r.tokens)))
            if len(r.tokens) >= 2:
                tpot.append((r.token_times[-1] - r.token_times[0])
                            / (len(r.tokens) - 1))
    notes.append(f"window: {len(calls)} calls, {window_s:.3f} s, "
                 f"{attempted} requests, {tokens} tokens, {n_comp} compiles "
                 f"({s_comp:.2f} s), {detections} detections, "
                 f"{gc_clock.count} gc pauses ({gc_clock.total_s:.3f} s, "
                 f"widest {gc_clock.worst_s:.3f} s)")
    slots = int(traffic["slots"])
    for i, c in enumerate(calls):
        st = call_stats(c, slots)
        notes.append(f"call {i}: {st['seconds']:.3f} s, {st['steps']} ticks, "
                     f"slot occupancy {st['occupancy_pct']:.1f}%, tail "
                     f"{st['tail_pct']:.1f}%, widest delivery gap "
                     f"{st['widest_gap_s']:.3f} s at "
                     f"{st['widest_gap_at_s']:.1f} s")

    data = None
    if ctx.trace:
        t_red = time.monotonic()
        data = _layer_data(ctx, cfg, traffic, calls[0], rec, rec_mono0,
                           max_len)
        obs.disable_trace()
        red = data.reduction
        notes.append(f"trace: {red.devices} devices, {len(red.ops_s)} op "
                     f"names, window {red.window_s:.3f} s, busy "
                     f"{red.busy_s:.3f} s, sedar_fingerprint "
                     f"{red.op_seconds('sedar_fingerprint')} s, "
                     f"{len(data.spans)} host spans, read in "
                     f"{time.monotonic() - t_red:.1f} s")

    # free the program before the reference runs
    del server, params, warm, reqs, out, rep, calls
    gc.collect()

    t_check = time.monotonic()
    sample_idx = check.sample_requests(done, ctx.seed,
                                       int(traffic["check_requests"]))
    sample = [done[i] for i in sample_idx]
    w = arch.weights.reference_weights(cfg, ctx.seed)
    reading = check.gap_readings(arch.reference, w, cfg, sample,
                                 pad_to=max_len, control=ctx.control)
    del w
    gap = reading["max_logit_gap"]
    if ctx.control:
        notes.append(f"control {ctx.control}: its tokens replace the served "
                     f"ones; the served tokens read {gap}")
        gap = reading["control_max_logit_gap"]
    limits = ctx.limits
    compared = {
        "failed_requests": {"value": failed,
                            "limit": limits["failed_requests"]},
        "max_logit_gap": {"value": gap, "limit": limits["max_logit_gap"]},
    }
    notes.append(f"check: {len(sample)} requests, "
                 f"{reading['served_tokens']} served tokens, "
                 f"{time.monotonic() - t_check:.1f} s")
    correct = all(c["value"] <= c["limit"] for c in compared.values()) \
        and attempted > 0
    return SimpleNamespace(
        correct=bool(correct), attempted=attempted, failed=failed,
        end_to_end={"serve_tokens_per_s": tokens / window_s,
                    "tpot_p90_ms": 1e3 * percentile(tpot, 90) if tpot
                    else float("nan"),
                    "setup_s": setup_s},
        memory_peak_bytes=memory_peak, compared=compared, notes=notes,
        data=data)


def _peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for d in devs)


def _layer_data(ctx, cfg, traffic, call, rec, rec_mono0, max_len
                ) -> SimpleNamespace:
    """What the per-layer readers read: the reduced trace of the traced
    call, the program's spans in it on the trace's clock, the call's work
    (prompt length, served tokens) and the configuration's `flops` module
    that counts it from shapes."""
    from jax.profiler import ProfileData
    from perf import trace_reduce
    paths = [os.path.join(dp, f) for dp, _, fs in os.walk(ctx.trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    pd = ProfileData.from_file(paths[0])
    start, end, offset = _profile_window(pd, call["a"] * 1e9)
    spans: List[Tuple[str, float, float, Dict[str, Any]]] = []
    for ev in rec.events:
        s = rec_mono0 * 1e9 + ev["ts"] * 1e3 + offset
        e = s + ev["dur"] * 1e3
        if e > start and s < end:
            spans.append((ev["name"], s, e, ev.get("args", {})))
    red = trace_reduce.reduce(pd, start, end,
                              [(n, s, e) for n, s, e, _ in spans])
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    work = [(r.prompt_len, len(r.tokens)) for r in call["reqs"]
            if r.status == "done"]
    replicas = 2 if traffic["backend"] in ("fused", "sequential") else 1
    return SimpleNamespace(
        reduction=red, spans=spans, cfg=cfg, traffic=traffic,
        peak=ctx.peak, chips=len(ctx.devices), max_len=max_len,
        replicas=replicas, call=call_stats(call, int(traffic["slots"])),
        work=work, flops=ctx.arch.flops)
