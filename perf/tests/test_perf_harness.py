"""The benchmark harness on the CPU at a tiny size.

A throwaway cell (its configuration, traffic mix and limits written as new
files, plus one entry in a BENCHMARK.json) runs by name through the same
code as the real cells. The harness's look for a chip is skipped; the rest
of a run is driven, sound and with the timed path broken underneath, and
`correct` must come out false for each fault a serving cell can have: a
token altered where it is produced, and a decode step that returns its
state (the KV cache) unchanged. A run with the float8 control in the
program's place (`--control fp8`) must come out not correct as well.

A second throwaway cell serves an architecture that exists only as new
files in the throwaway checkout: a configuration naming its own
`reference`, `weights` and `flops` modules (copied from
`perf/testdata/untied/` to paths no harness file names), for a decoder with
no QKV bias and an untied head, so that the comparison reads logits from a
head of their own.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import run as bench_run  # noqa: E402

CELL = "tiny.mix"
CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
# sound bfloat16 runs of this cell read 0 to 0.0034 and its float8 control
# 0.074 to 0.079 (three seeds each on the CPU); the limit lies between
TINY_LIMIT = 0.03
QWEN2_ARCH = {"reference": "perf/reference/qwen2.py",
              "weights": "perf/weights/qwen2.py",
              "flops": "perf/flops/qwen2.py"}
TINY_CFG = {"name": "tiny", "source": "test", "hidden_act": "silu",
            "hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 257,
            "rms_norm_eps": 1e-6, "rope_theta": 1e6,
            "tie_word_embeddings": True, "param_dtype": "float32",
            "dtype": "bfloat16", "cache_dtype": "bfloat16", "reduced": [],
            **QWEN2_ARCH}
UNTIED_CELL = "tiny-untied.mix"
# sound bfloat16 runs of this cell read 0 to 0.011 and its float8 control
# 0.387 to 0.641 (four seeds each on the CPU; its head, drawn at std
# 1/sqrt(hidden), gives wider logits than the tied 0.02 embedding); the
# limit lies between, 4.5x over the one and 7.7x under the other
UNTIED_LIMIT = 0.05
UNTIED_ARCH = {key: f"perf/untied_arch/{key}.py" for key in QWEN2_ARCH}
UNTIED_CFG = dict(TINY_CFG, name="tiny-untied", tie_word_embeddings=False,
                  **UNTIED_ARCH)


@pytest.fixture(scope="module")
def cell_root(tmp_path_factory):
    """A checkout-like directory holding a throwaway cell as data files."""
    root = tmp_path_factory.mktemp("cell")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(os.path.join(ROOT, "perf", "traffic",
                           "serve.fused.chat.json")) as fh:
        mix = json.load(fh)
    mix.update(slots=4, buckets=[8, 16, 32], requests_per_call=8,
               prompt_len={"median": 12, "sigma": 0.5, "min": 4, "max": 30},
               output_len={"median": 8, "sigma": 0.5, "min": 2, "max": 16},
               warmup_max_output=4, check_requests=3)
    bench["configs"] = [{"name": c["name"], "source": "test",
                         "file": f"perf/configs/{c['name']}.json",
                         "reduced": [], "why": "test"}
                        for c in (TINY_CFG, UNTIED_CFG)]
    bench["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "mix",
                           "chips": 1, "why": "test"},
                          {"name": UNTIED_CELL, "config": "tiny-untied",
                           "traffic": "mix", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL, UNTIED_CELL]
    files = {"BENCHMARK.json": bench, "perf/configs/tiny.json": TINY_CFG,
             "perf/configs/tiny-untied.json": UNTIED_CFG,
             "perf/traffic/mix.json": mix,
             "perf/limits/tiny.mix.json": {"failed_requests": 0,
                                           "max_logit_gap": TINY_LIMIT},
             "perf/limits/tiny-untied.mix.json": {
                 "failed_requests": 0, "max_logit_gap": UNTIED_LIMIT}}
    for rel, doc in files.items():
        os.makedirs(os.path.dirname(os.path.join(root, rel)), exist_ok=True)
        with open(os.path.join(root, rel), "w") as fh:
            json.dump(doc, fh)
    # a checkout holds the qwen2 modules where the repository does; the
    # second architecture's modules are new files at new paths
    copies = {rel: rel for rel in QWEN2_ARCH.values()}
    copies.update({f"perf/testdata/untied/{key}.py": rel
                   for key, rel in UNTIED_ARCH.items()})
    for src, rel in copies.items():
        os.makedirs(os.path.dirname(os.path.join(root, rel)), exist_ok=True)
        shutil.copy(os.path.join(ROOT, src), os.path.join(root, rel))
    return str(root)


def run_cell(root, capsys, seed, trace=0, extra=(), cell=CELL):
    rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", "0.5", "--trace", str(trace), *extra],
                        root=root, require_tpu=False, peaks=CPU_PEAKS,
                        compile_cache=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "perf/run.py", "--workload",
                        "qwen2-0.5b.serve.fused.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_device_kind_exits_nonzero_without_a_result(cell_root,
                                                            capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "1", "--seconds", "1"],
                        root=cell_root, require_tpu=False, peaks={},
                        compile_cache=False)
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_every_metric_has_a_reader_and_every_cell_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "perf", "metrics",
                                           m["name"] + ".py")), m["name"]
    for wl in bench["workloads"]:
        cell = bench_run.load_cell(ROOT, wl["name"])
        assert cell.limits and cell.cfg and cell.traffic
        e2e = bench_run.cell_metrics(bench, wl["name"], "end_to_end")
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        assert bench_run.cell_metrics(bench, wl["name"], "per_layer")
    # every configuration names its architecture's modules under the
    # benchmark's paths; `load_arch` refuses one that is missing or lacks a
    # function of the interface
    for centry in bench["configs"]:
        with open(os.path.join(ROOT, centry["file"])) as fh:
            cfg = json.load(fh)
        bench_run.load_arch(ROOT, cfg)
        for key in bench_run.ARCH:
            assert cfg[key].split("/")[0] in bench["paths"], cfg[key]


@pytest.mark.parametrize("key", sorted(bench_run.ARCH))
def test_configuration_without_its_modules_is_refused(key):
    """A configuration that names no module for a key, a module that is not
    there, or one that lacks a function of the interface, is a cell that
    cannot run."""
    cfg = dict(TINY_CFG)
    del cfg[key]
    with pytest.raises(bench_run.CellError, match=f"no {key} module"):
        bench_run.load_arch(ROOT, cfg)
    with pytest.raises(bench_run.CellError, match="missing module"):
        bench_run.load_arch(ROOT, dict(cfg, **{key: "perf/nowhere.py"}))
    with pytest.raises(bench_run.CellError, match="lacks"):
        bench_run.load_arch(ROOT, dict(cfg, **{key: "perf/compiles.py"}))


def test_throwaway_mix_runs_by_name_and_is_correct(cell_root, capsys):
    line = run_cell(cell_root, capsys, seed=2**31 + 11)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 8
    assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p90_ms",
                                    "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert list(line)[-1] == "compared"
    assert line["compared"]["max_logit_gap"]["value"] <= TINY_LIMIT


def test_traced_run_reports_per_layer_metrics(cell_root, capsys):
    line = run_cell(cell_root, capsys, seed=2**31 + 12, trace=1)
    assert line["correct"] is True
    # the CPU has no device plane: device metrics are left out, span
    # metrics are read
    assert set(line["metrics"]) == {"decode_tick_ms.serve",
                                    "deferred_flush_ms.serve",
                                    "slot_occupancy_pct.serve"}
    assert 0 < line["metrics"]["slot_occupancy_pct.serve"]["value"] <= 100
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def _altered_token(orig):
    def decode(cfg, params, cache, tokens, pos, ctx=None):
        import jax
        import jax.numpy as jnp
        logits, cache = orig(cfg, params, cache, tokens, pos, ctx)
        wrong = (jnp.argmax(logits, -1) + 1) % logits.shape[-1]
        return logits + 1e4 * jax.nn.one_hot(wrong, logits.shape[-1],
                                             dtype=logits.dtype), cache
    return decode


def _state_unchanged(orig):
    def decode(cfg, params, cache, tokens, pos, ctx=None):
        logits, _new = orig(cfg, params, cache, tokens, pos, ctx)
        return logits, cache
    return decode


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(cell_root, capsys, monkeypatch,
                                          fault):
    from repro.models import transformer
    monkeypatch.setattr(transformer, "lm_decode_step",
                        fault(transformer.lm_decode_step))
    line = run_cell(cell_root, capsys, seed=2**31 + 13)
    assert line["correct"] is False
    assert line["compared"]["max_logit_gap"]["value"] > TINY_LIMIT


def test_fp8_control_fails_the_limit(cell_root, capsys):
    line = run_cell(cell_root, capsys, seed=2**31 + 5,
                    extra=("--control", "fp8"))
    assert line["correct"] is False
    assert line["failed"] == 0
    assert line["compared"]["max_logit_gap"]["value"] > TINY_LIMIT


def test_second_architecture_runs_by_name_from_new_files(cell_root, capsys):
    cell = bench_run.load_cell(cell_root, UNTIED_CELL)
    for key, rel in UNTIED_ARCH.items():
        assert getattr(cell.arch, key).__file__ == os.path.join(cell_root,
                                                                rel)
        assert not os.path.exists(os.path.join(ROOT, rel))
    line = run_cell(cell_root, capsys, seed=2**31 + 21, cell=UNTIED_CELL)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 8
    assert line["compared"]["max_logit_gap"]["value"] <= UNTIED_LIMIT


@pytest.mark.parametrize("broken", ["token_altered", "fp8_control"])
def test_second_architecture_broken_is_not_correct(cell_root, capsys,
                                                   monkeypatch, broken):
    extra = ()
    if broken == "token_altered":
        from repro.models import transformer
        monkeypatch.setattr(transformer, "lm_decode_step",
                            _altered_token(transformer.lm_decode_step))
    else:
        extra = ("--control", "fp8")
    line = run_cell(cell_root, capsys, seed=2**31 + 23, extra=extra,
                    cell=UNTIED_CELL)
    assert line["correct"] is False
    assert line["compared"]["max_logit_gap"]["value"] > UNTIED_LIMIT
