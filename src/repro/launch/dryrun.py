"""Multi-pod dry-run: lower + compile every (arch x shape x mesh x flavor)
cell on the production mesh and extract memory / cost / collective analyses.

    PYTHONPATH=src python -m repro.launch.dryrun --arch mistral-large-123b \
        --shape train_4k --mesh single --flavor baseline

Artifacts: one JSON per cell under artifacts/dryrun/.

Flavors:
  baseline : no protection; multi-pod meshes use the pod axis for data
             parallelism (batch over ("pod","data")).
  sedar    : the paper's dual-modular-redundant training step — the pod axis
             carries the two replicas, gradient fingerprints are exchanged
             over it (shard_map all-gather) and the commit is gated on the
             comparison. Proves the paper's mechanism lowers/shards at
             production scale.

Scan-cost correction (DESIGN.md §7): XLA counts each scan body once, so every
cell also lowers the model's Probe programs; corrected totals are
    total = full_program + sum_i multiplier_i * probe_i.
"""
# The VERY FIRST lines, before ANY other import (jax locks the device count
# on first init):
import os                                                     # noqa: E402
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse     # noqa: E402
import dataclasses  # noqa: E402
import json         # noqa: E402
import re           # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import (SHAPES, SHAPE_BY_NAME, get_config,  # noqa: E402
                           shape_applicable, ASSIGNED_ARCHS)
from repro.launch import input_specs as ispec  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import apply_updates, make_optimizer  # noqa: E402
from repro.sharding import Resolver, ShardingRules  # noqa: E402

# -- hardware model (TPU v5e, task spec) ---------------------------------------
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def parse_collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum per-device result bytes of every collective op in compiled HLO."""
    per_kind: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        nbytes = 0
        for sm in _SHAPE_RE.finditer(type_str):
            dt, dims = sm.group(1), sm.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        per_kind[kind] = per_kind.get(kind, 0) + nbytes
        count[kind] = count.get(kind, 0) + 1
    return {"bytes_per_kind": per_kind, "count_per_kind": count,
            "total_bytes": sum(per_kind.values())}


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
# NB: the while argument may contain nested parens (older jax prints the
# full tuple type: `while((s32[], f32[...]) %tuple.10), condition=...`), so
# the argument is matched non-greedily up to the condition/body attributes.
_WHILE_RE = re.compile(
    r"\bwhile\(.*?(?:condition=%?([\w.\-]+)\s*,\s*body=%?([\w.\-]+)"
    r"|body=%?([\w.\-]+)\s*,\s*condition=%?([\w.\-]+))")
_CALL_RE = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)="
                      r"\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")


def parse_collective_bytes_loopaware(hlo_text: str) -> Dict[str, Any]:
    """Exact collective accounting: per-computation collective bytes weighted
    by the product of enclosing while-loop trip counts (scan bodies execute
    trip-count times, not once). Trip counts come from the s32 constants in
    each loop's condition computation (max constant = loop bound).

    This reads the REAL compiled program, so there are no probe-isolation
    artifacts; it is the collective source of truth for the roofline."""
    comps: Dict[str, dict] = {}
    cur = None
    entry = None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line)
        if m and ("->" in line):
            cur = m.group(1)
            comps[cur] = {"coll": {}, "whiles": [], "consts": [], "calls": []}
            if line.lstrip().startswith("ENTRY"):
                entry = cur
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            continue
        cm = _COLL_RE.search(line)
        if cm:
            nbytes = 0
            for sm in _SHAPE_RE.finditer(cm.group(1)):
                dt, dims = sm.group(1), sm.group(2)
                if dt not in _DTYPE_BYTES:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                nbytes += n * _DTYPE_BYTES[dt]
            kind = cm.group(2)
            comps[cur]["coll"][kind] = comps[cur]["coll"].get(kind, 0) + nbytes
        wm = _WHILE_RE.search(line)
        if wm:
            cond = wm.group(1) or wm.group(4)
            body = wm.group(2) or wm.group(3)
            comps[cur]["whiles"].append((cond, body))
        elif "=" in line:
            for dm in _CALL_RE.finditer(line):
                for name in dm.group(1).split(","):
                    comps[cur]["calls"].append(name.strip().lstrip("%"))
        for km in _CONST_RE.finditer(line):
            comps[cur]["consts"].append(int(km.group(1)))

    def trip_count(cond: str) -> int:
        cs = comps.get(cond, {}).get("consts", [])
        return max([c for c in cs if c > 0] or [1])

    totals: Dict[str, float] = {}
    counted = {}

    def visit(name: str, mult: float, depth=0):
        if name not in comps or depth > 12:
            return
        c = comps[name]
        for kind, b in c["coll"].items():
            totals[kind] = totals.get(kind, 0.0) + mult * b
        for cond, body in c["whiles"]:
            visit(body, mult * trip_count(cond), depth + 1)
            visit(cond, mult * trip_count(cond), depth + 1)
        for callee in c["calls"]:
            if callee in comps and callee != name:
                visit(callee, mult, depth + 1)

    if entry:
        visit(entry, 1.0)
    return {"bytes_per_kind": {k: float(v) for k, v in totals.items()},
            "total_bytes": float(sum(totals.values()))}


def _analyze(compiled) -> Dict[str, Any]:
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):     # older jax: one dict per partition
        ca = ca[0] if ca else {}
    text = compiled.as_text()
    coll = parse_collective_bytes(text)
    coll_loop = parse_collective_bytes_loopaware(text)
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "collective": coll,
            "collective_loopaware": coll_loop}


# ---------------------------------------------------------------------------
# Program builders
# ---------------------------------------------------------------------------

def _half_params(params):
    """Pre-cast f32 masters to bf16 BEFORE the per-layer FSDP all-gathers, so
    weight gathers and gradient reduce-scatters move bf16 (2x less ICI)."""
    return jax.tree.map(
        lambda p: p.astype(jnp.bfloat16) if p.dtype == jnp.float32 else p,
        params)


def build_train_program(cfg, shape, mesh, resolver, flavor, train_cfg=None,
                        microbatches: int = 1):
    """Full train step: grads (accumulated over `microbatches`) + AdamW commit.

    Gradient accumulation is the standard fit-the-batch mechanism at 100B
    scale: per-microbatch activations shrink by M while the f32 accumulator
    costs one params-sized buffer. The dry-run auto-raises M until the cell
    fits HBM (recorded in the artifact)."""
    from repro.configs.base import TrainConfig
    from repro.models.transformer import ShardCtx
    model = build_model(cfg)
    opt = make_optimizer(train_cfg or TrainConfig())
    ctx = ShardCtx(mesh, resolver)
    M = microbatches

    state_specs, state_axes = ispec.train_state_specs(cfg)
    bspecs, baxes = ispec.batch_specs(cfg, shape)

    pshard = resolver.tree_shardings(state_axes["params"],
                                     state_specs["params"]) \
        if hasattr(resolver, "tree_shardings") else None

    def _pin_grads(grads):
        """Constrain bf16 grads to the parameter sharding BEFORE the f32
        cast, so the cross-data reduction moves bf16 (reduce-scatter), not
        f32 partials (§Perf C9)."""
        if pshard is None:
            return grads
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s), grads, pshard)

    def accumulate_grads(half, batch):
        def loss_fn(ph, b):
            return model.loss(ph, b, ctx)[0]

        if M <= 1:
            loss, grads = jax.value_and_grad(loss_fn)(half, batch)
            grads = _pin_grads(grads)
            return loss, jax.tree.map(lambda g: g.astype(jnp.float32), grads)

        mb = jax.tree.map(
            lambda a: a.reshape((M, a.shape[0] // M) + a.shape[1:]), batch)
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), half)

        def micro(acc, b):
            loss, g = jax.value_and_grad(loss_fn)(half, b)
            g = _pin_grads(g)
            acc = jax.tree.map(
                lambda a, gg: a + gg.astype(jnp.float32) / M, acc, g)
            return acc, loss

        grads, losses = jax.lax.scan(micro, zeros, mb)
        return jnp.mean(losses), grads

    if flavor == "sedar":
        from repro.core.detection import make_pod_comparator
        from repro.core.fingerprint import pytree_fingerprint
        pod_cmp = make_pod_comparator(mesh, "pod")

        def step(state, batch):
            half = _half_params(state["params"])
            loss, grads = accumulate_grads(half, batch)
            fp = pytree_fingerprint(grads)
            eq, fp_all = pod_cmp(fp)
            updates, new_opt = opt.update(grads, state["opt"],
                                          state["params"], state["step"])
            new_params = apply_updates(state["params"], updates)
            cand = {"params": new_params, "opt": new_opt,
                    "step": state["step"] + 1}
            # Commit gating is RUNTIME-side at production scale: an in-jit
            # where(eq, cand, state) select keeps two full TrainStates live
            # (+~params*12 bytes/chip at 123B — the difference between
            # fitting HBM and not). The runtime reads `eq` before the state
            # is checkpointed or otherwise externalized, so the paper's
            # containment ("never send corrupted data") holds; a mismatch
            # triggers L2/L3 rollback of the uncommitted step instead.
            return cand, (loss, eq, fp_all)
    else:
        def step(state, batch):
            half = _half_params(state["params"])
            loss, grads = accumulate_grads(half, batch)
            updates, new_opt = opt.update(grads, state["opt"],
                                          state["params"], state["step"])
            new_params = apply_updates(state["params"], updates)
            return ({"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}, loss)

    in_shardings = (resolver.tree_shardings(state_axes, state_specs),
                    resolver.tree_shardings(baxes, bspecs))
    fn = jax.jit(step, in_shardings=in_shardings, donate_argnums=(0,))
    return fn, (state_specs, bspecs)


def build_prefill_program(cfg, shape, mesh, resolver):
    from repro.models.transformer import ShardCtx
    model = build_model(cfg)
    ctx = ShardCtx(mesh, resolver)
    pspecs, paxes = ispec.serve_param_specs(cfg)
    bspecs, baxes = ispec.batch_specs(cfg, shape)

    # decode cache must hold prompt + visual prefix for VLM archs
    max_len = shape.seq_len + (cfg.frontend_seq if cfg.family == "vlm" else 0)

    def prefill(params, batch):
        return model.prefill(params, batch, max_len, ctx)

    in_shardings = (resolver.tree_shardings(paxes, pspecs),
                    resolver.tree_shardings(baxes, bspecs))
    fn = jax.jit(prefill, in_shardings=in_shardings)
    return fn, (pspecs, bspecs)


def build_decode_program(cfg, shape, mesh, resolver):
    from repro.models.transformer import ShardCtx
    model = build_model(cfg)
    ctx = ShardCtx(mesh, resolver)
    pspecs, paxes = ispec.serve_param_specs(cfg)
    dspecs, daxes = ispec.decode_specs(cfg, shape)

    def decode(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos, ctx)

    in_shardings = (resolver.tree_shardings(paxes, pspecs),
                    resolver.tree_shardings(daxes["cache"], dspecs["cache"]),
                    resolver.tree_shardings(daxes["tokens"], dspecs["tokens"]),
                    None)
    fn = jax.jit(decode, in_shardings=in_shardings, donate_argnums=(1,))
    return fn, (pspecs, dspecs["cache"], dspecs["tokens"], dspecs["pos"])


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_kind: str, flavor: str,
             out_dir: str, with_probes: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPE_BY_NAME[shape_name]
    t0 = time.time()
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
            "flavor": flavor}

    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        cell.update({"status": "skipped", "reason": reason})
        return _emit(cell, out_dir)

    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    chips = mesh.devices.size
    # sequence-parallel activation sharding for full-sequence programs: the
    # residual-stream carries saved by the layer scans shard over the model
    # axis (Megatron-SP), which is what lets the biggest train cells fit HBM.
    # Hillclimb knobs (recorded in the artifact): REPRO_NO_SEQP=1 disables
    # SP; REPRO_MICRO=n pins the accumulation factor; REPRO_REMAT overrides
    # the remat policy.
    seqp = shape.kind != "decode" and not os.environ.get("REPRO_NO_SEQP")
    if os.environ.get("REPRO_REMAT"):
        cfg = dataclasses.replace(cfg, remat=os.environ["REPRO_REMAT"])
    cell["knobs"] = {"seqp": seqp, "remat": cfg.remat,
                     "forced_micro": os.environ.get("REPRO_MICRO")}
    if flavor == "sedar":
        if not multi:
            cell.update({"status": "skipped",
                         "reason": "sedar flavor needs the pod axis"})
            return _emit(cell, out_dir)
        rules = ShardingRules(data_axes=("data",),        # pod = replica axis
                              sequence_parallel=seqp)
    else:
        rules = ShardingRules(data_axes=(("pod", "data") if multi
                                         else ("data",)),
                              sequence_parallel=seqp)
    resolver = Resolver(mesh, rules)

    micro = int(os.environ.get("REPRO_MICRO", 1))
    HBM = 16 * 2**30
    try:
        with mesh:
            if shape.kind == "train":
                # auto-raise gradient-accumulation factor until the cell fits
                while True:
                    fn, args = build_train_program(cfg, shape, mesh, resolver,
                                                   flavor, microbatches=micro)
                    compiled = fn.lower(*args).compile()
                    ma = compiled.memory_analysis()
                    per_dev = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                               + ma.output_size_in_bytes - ma.alias_size_in_bytes)
                    B = shape.global_batch
                    # sedar-dual pods carry the FULL replica batch (the
                    # paper's 2x redundancy), so allow deeper accumulation
                    cap = 32 if flavor == "sedar" else 16
                    if per_dev <= HBM or micro * 2 > min(cap, B):
                        break
                    micro *= 2
            elif shape.kind == "prefill":
                fn, args = build_prefill_program(cfg, shape, mesh, resolver)
                compiled = fn.lower(*args).compile()
                ma = compiled.memory_analysis()
            else:
                fn, args = build_decode_program(cfg, shape, mesh, resolver)
                compiled = fn.lower(*args).compile()
                ma = compiled.memory_analysis()
            full = _analyze(compiled)
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug, record it
        cell.update({"status": "failed", "error": f"{type(e).__name__}: {e}",
                     "traceback": traceback.format_exc()[-2000:]})
        return _emit(cell, out_dir)
    cell["microbatches"] = micro

    per_dev_bytes = int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                        + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    cell["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "per_device_bytes": per_dev_bytes,
        "fits_16GiB": bool(per_dev_bytes <= 16 * 2**30),
    }
    cell["full_program"] = full

    # -- corrections -------------------------------------------------------------
    # Collectives: the loop-aware HLO walk of the REAL program is exact
    # (trip-count-weighted), so probes contribute nothing there. FLOPs/bytes:
    # cost_analysis has no per-op attribution, so scan bodies are corrected
    # with probe programs; with gradient accumulation the per-microbatch
    # structure repeats M times:
    #   total = full + (M-1)*P_micro + M * sum_i mult_i * P_i(micro shape)
    model = build_model(cfg)
    tot_flops, tot_bytes = full["flops"], full["bytes"]
    tot_coll = float(full["collective_loopaware"]["total_bytes"])
    probes_out = []
    if with_probes:
        probe_shape = (dataclasses.replace(
            shape, global_batch=shape.global_batch // micro)
            if micro > 1 else shape)
        scale = micro if shape.kind == "train" else 1
        probe_list = list(model.probes(probe_shape))
        if micro > 1:
            from repro.models.model import Probe, _grad_probe
            hspecs, haxes = ispec.serve_param_specs(cfg)   # bf16 weights
            mb_specs, mb_axes = ispec.batch_specs(cfg, probe_shape)

            def loss_micro(ph, b):
                return model.loss(ph, b, None)[0]

            probe_list.append(Probe("micro", _grad_probe(loss_micro),
                                    (hspecs, mb_specs), (haxes, mb_axes),
                                    multiplier=(micro - 1) / scale))
        try:
            with mesh:
                for p in probe_list:
                    shardings = tuple(
                        resolver.tree_shardings(ax, sp)
                        for ax, sp in zip(p.arg_axes, p.arg_specs))
                    pc = _lower_probe(mesh, p, shardings)
                    pa = _analyze(pc)
                    mult = p.multiplier * scale
                    probes_out.append({"name": p.name,
                                       "multiplier": mult,
                                       **{k: pa[k] for k in ("flops", "bytes")},
                                       "collective_bytes":
                                       float(pa["collective"]["total_bytes"])})
                    tot_flops += mult * pa["flops"]
                    tot_bytes += mult * pa["bytes"]
        except Exception as e:  # noqa: BLE001
            cell["probe_error"] = f"{type(e).__name__}: {e}"
    cell["probes"] = probes_out

    # -- roofline terms (per task spec; quantities are per-device) ---------------
    compute_s = tot_flops / PEAK_FLOPS
    memory_s = tot_bytes / HBM_BW
    coll_s = tot_coll / ICI_BW
    dominant = max((compute_s, "compute"), (memory_s, "memory"),
                   (coll_s, "collective"))[1]

    n_params = model.param_count()
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * tokens
    else:
        model_flops = 2 * n_active * shape.global_batch

    hlo_flops_global = tot_flops * chips
    cell.update({
        "status": "ok",
        "chips": int(chips),
        "corrected": {"flops_per_device": tot_flops,
                      "bytes_per_device": tot_bytes,
                      "collective_bytes_per_device": tot_coll},
        "roofline": {"compute_s": compute_s, "memory_s": memory_s,
                     "collective_s": coll_s, "dominant": dominant,
                     "bound_s": max(compute_s, memory_s, coll_s)},
        "model_flops": float(model_flops),
        "hlo_flops_global": float(hlo_flops_global),
        "useful_flops_ratio": float(model_flops / hlo_flops_global)
        if hlo_flops_global else 0.0,
        "params": int(n_params),
        "active_params": int(n_active),
        "sharding_fallbacks": resolver.fallback_report()[:40],
        "elapsed_s": round(time.time() - t0, 1),
    })
    return _emit(cell, out_dir)


def _lower_probe(mesh, p, shardings):
    """Grad probes return (value, [grads-of-float-args]); pin the grads to
    their argument shardings so XLA does not append replication all-reduces
    that the real in-loop program never performs."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    leaves, _ = jax.tree_util.tree_flatten(p.arg_specs)
    sh_leaves = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    float_sh = [s for l, s in zip(leaves, sh_leaves)
                if jnp.issubdtype(l.dtype, jnp.floating)]
    scalar = NamedSharding(mesh, P())
    try:
        fn = jax.jit(p.fn, in_shardings=shardings,
                     out_shardings=(scalar, float_sh))
        return fn.lower(*p.arg_specs).compile()
    except (TypeError, ValueError):
        return jax.jit(p.fn, in_shardings=shardings)             .lower(*p.arg_specs).compile()


def _emit(cell: Dict[str, Any], out_dir: str) -> Dict[str, Any]:
    os.makedirs(out_dir, exist_ok=True)
    name = f"{cell['arch']}__{cell['shape']}__{cell['mesh']}__{cell['flavor']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(cell, f, indent=1, default=str)
    status = cell.get("status")
    roof = cell.get("roofline", {})
    print(f"[dryrun] {cell['arch']:24s} {cell['shape']:12s} {cell['mesh']:6s} "
          f"{cell['flavor']:8s} {status:8s} "
          f"dom={roof.get('dominant', '-'):10s} "
          f"fit={cell.get('memory', {}).get('fits_16GiB', '-')} "
          f"t={cell.get('elapsed_s', '-')}s"
          + (f" err={cell.get('error', '')[:90]}" if status == "failed" else ""),
          flush=True)
    return cell


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--flavor", default="baseline",
                    choices=["baseline", "sedar", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-probes", action="store_true")
    args = ap.parse_args()

    archs = ASSIGNED_ARCHS if args.arch == "all" else args.arch.split(",")
    shapes = [s.name for s in SHAPES] if args.shape == "all" \
        else args.shape.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    flavors = ["baseline", "sedar"] if args.flavor == "both" else [args.flavor]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                for fl in flavors:
                    if fl == "sedar" and (mk != "multi" or shape != "train_4k"):
                        continue
                    cell = run_cell(arch, shape, mk, fl, args.out,
                                    with_probes=not args.no_probes)
                    if cell.get("status") == "failed":
                        n_fail += 1
    print(f"[dryrun] done, failures: {n_fail}", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
