"""Run one benchmark cell once, on the chip this process finds.

    python3 perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from `BENCHMARK.json`: the cell (`workloads`)
names a configuration (the config entry's `file`) and a traffic mix or
job (`perf/traffic/<traffic>.json`). The traffic file's `job` names the
driver in `perf/jobs/` and its `kind` the generator in `perf/traffic/`;
the cell's correctness limits are `perf/limits/<workload>.json`, and each
per-layer metric is read by `perf/metrics/<metric>.py`. A new cell is one
entry in `BENCHMARK.json` plus such files.

The configuration file names, by paths from the checkout's root, the
three modules that know its architecture (`ARCH`): its plain `reference`,
its `weights` and its `flops`. No other file of the harness knows one. A
new architecture is a configuration naming its three modules, plus those
modules.

The run exits non-zero and prints no result when JAX finds no TPU, fewer
chips than the cell asks for, or a device kind that `perf/peaks.json` does
not list. Otherwise its last stdout line is one JSON object with
`correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end metrics
with `--trace 0`, its per-layer metrics with `--trace 1`), `device`,
`breakdown` (traced runs) and, last, `compared`: each number that decided
`correct` beside its limit. The same numbers are the last lines of stderr.

`--control fp8` puts the reference, computed in float8, in the program's
place in that comparison; such a run has to come out not correct. The
benchmark's own runs never pass it.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

PERF = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


# What each module that a configuration names gives the harness: the
# reference's `hidden(w, cfg, tokens, quant=None)` and `logits(w, h,
# quant=None)` over its whole weight dict `w`; `reference_weights(cfg,
# seed)`, `program_weights(cfg, seed)` and the program's `model_config(cfg)`;
# `serving_flops(cfg, work)` and `prefill_lane_bytes(cfg, max_len, rows,
# replicas)`.
ARCH = {"reference": ("hidden", "logits"),
        "weights": ("reference_weights", "program_weights", "model_config"),
        "flops": ("serving_flops", "prefill_lane_bytes")}


class CellError(RuntimeError):
    """The cell cannot run here; no result is printed."""


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise CellError(f"missing module {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_arch(root: str, cfg: Dict[str, Any]) -> SimpleNamespace:
    """The modules that a configuration names for its architecture."""
    mods = {}
    for key, names in ARCH.items():
        if key not in cfg:
            raise CellError(f"configuration {cfg['name']!r} names no {key} "
                            "module")
        mod = load_module(os.path.join(root, cfg[key]),
                          f"perf_{key}_{cfg['name']}".replace("-", "_")
                          .replace(".", "_"))
        lacks = [n for n in names if not callable(getattr(mod, n, None))]
        if lacks:
            raise CellError(f"{cfg[key]} lacks {', '.join(lacks)}")
        mods[key] = mod
    return SimpleNamespace(**mods)


def cell_metrics(bench: Dict[str, Any], workload: str, section: str
                 ) -> List[Dict[str, Any]]:
    """The metrics of `section` that this cell reports."""
    e2e = [m["name"] for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if section == "end_to_end":
        return [m for m in bench["end_to_end"] if m["name"] in e2e]
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def load_cell(root: str, workload: str) -> SimpleNamespace:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise CellError(f"no BENCHMARK.json in {root}")
    bench = load_json(path)
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json")
    centry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    perf = os.path.join(root, "perf")
    try:
        cfg = load_json(os.path.join(root, centry["file"]))
        return SimpleNamespace(
            bench=bench, workload=wl, name=workload, cfg=cfg,
            arch=load_arch(root, cfg),
            traffic=load_json(os.path.join(perf, "traffic",
                                           wl["traffic"] + ".json")),
            limits=load_json(os.path.join(perf, "limits",
                                          workload + ".json")))
    except FileNotFoundError as e:
        raise CellError(f"cell {workload!r} lacks a file: {e}") from e


def find_devices(chips: int, peaks: Dict[str, Any], require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise CellError(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise CellError(f"the cell needs {chips} chips, JAX found "
                        f"{len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise CellError(f"perf/peaks.json has no entry for {kind!r}")
    return devs, peaks[kind]


def read_per_layer(metrics: List[Dict[str, Any]], data) -> Dict[str, Any]:
    out = {}
    for m in metrics:
        reader = load_module(os.path.join(PERF, "metrics", m["name"] + ".py"),
                             "perf_metric_" + m["name"].replace(".", "_"))
        value = reader.read(data)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def enable_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed place,
    small programs too, so that a warm run compiles nothing at all."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv: Optional[List[str]] = None, *, root: str = ROOT,
         require_tpu: bool = True, peaks: Optional[Dict[str, Any]] = None,
         compile_cache: bool = True) -> int:
    """`require_tpu`, `peaks` and `compile_cache` exist for the CPU tests,
    which drive a run at a tiny size without a chip."""
    t_start = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("fp8",), default=None)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(root, args.workload)
        if peaks is None:
            peaks = load_json(os.path.join(PERF, "peaks.json"))
        devs, peak = find_devices(cell.workload["chips"], peaks, require_tpu)
    except CellError as e:
        print(f"perf/run.py: {e}", file=sys.stderr)
        return 2

    if compile_cache:
        enable_cache()
    traffic = cell.traffic
    job = load_module(os.path.join(PERF, "jobs", traffic["job"] + ".py"),
                      "perf_job_" + traffic["job"])
    gen = load_module(os.path.join(PERF, "traffic", traffic["kind"] + ".py"),
                      "perf_traffic_" + traffic["kind"])
    ctx = SimpleNamespace(
        cfg=cell.cfg, arch=cell.arch, traffic=traffic, gen=gen,
        limits=cell.limits,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        control=args.control,
        devices=devs[:cell.workload["chips"]], peak=peak, t_start=t_start,
        trace_dir=os.path.join(root, ".perf_trace", args.workload))
    res = job.run(ctx)

    if args.trace:
        metrics = read_per_layer(
            cell_metrics(cell.bench, args.workload, "per_layer"), res.data)
    else:
        metrics = {m["name"]: {"value": res.end_to_end[m["name"]],
                               "unit": m["unit"]}
                   for m in cell_metrics(cell.bench, args.workload,
                                         "end_to_end")}
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": res.memory_peak_bytes}
    line: Dict[str, Any] = {"correct": res.correct, "attempted": res.attempted,
                            "failed": res.failed, "metrics": metrics,
                            "device": device}
    if args.trace:
        device["busy_s"] = res.data.reduction.busy_s
        device["window_s"] = res.data.reduction.window_s
        line["breakdown"] = {
            "device_ops": res.data.reduction.top_ops(10),
            "idle_gaps": res.data.reduction.top_idle(10)}
    for note in res.notes:
        print(note, file=sys.stderr)
    line["compared"] = res.compared
    for name, c in res.compared.items():
        print(f"compared {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
