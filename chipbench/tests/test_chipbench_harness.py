"""The harness is driven by data: every cell, configuration, mix, runner and
metric is found by name, and a new one needs only new files and entries."""
import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import run
from chipbench.tests import tiny

ROOT = tiny.CHIPBENCH.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_workload_resolves_to_its_files(name):
    cell = run.resolve(name)
    w = cell.workload
    assert cell.cfg["name"] == w["config"] and cell.chips == w["chips"]
    assert cell.cfg["chips"] == w["chips"]
    importlib.import_module(f"chipbench.runners.{cell.cfg['runner']}").Run
    arch = importlib.import_module(f"chipbench.archs.{cell.cfg['model_type']}")
    assert callable(arch.arch) and callable(arch.work)
    importlib.import_module(f"chipbench.reference.{cell.cfg['model_type']}").make_step
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for kind, metrics in (("end_to_end", cell.end_to_end), ("layer_metrics", cell.per_layer)):
        for m in metrics:
            assert callable(importlib.import_module(f"chipbench.{kind}.{m['name']}").read)
    for m in cell.per_layer:
        assert m["moves"] in e2e


def test_every_configuration_states_its_cut_and_limits():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["source"] == c["source"]
        assert set(cfg["check"]["limits"]) == {"loss_gap", "grad_norm_gap", "change_gap"}


def test_a_cell_added_as_new_files_and_entries_is_found(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = tmp_path / "chipbench"
    cfg = json.loads((base / "configs" / "qwen3-8b-1chip.json").read_text())
    cfg["train"]["seq_len"] = 8192
    (base / "configs" / "qwen3-8b-1chip-s8k.json").write_text(json.dumps(cfg))
    mix = json.loads((base / "traffic" / "packed.json").read_text())
    mix["doc_lengths"]["mu"] = 7.0
    (base / "traffic" / "longer_docs.json").write_text(json.dumps(mix))
    (base / "layer_metrics" / "steps_in_window.py").write_text(
        "def read(ctx):\n    return len(ctx.steps)\n")
    bench["configs"].append({"name": "qwen3-8b-1chip-s8k", "source": cfg["source"],
                             "file": "chipbench/configs/qwen3-8b-1chip-s8k.json",
                             "reduced": ["num_hidden_layers", "vocab_size"], "why": "test"})
    bench["workloads"].append({"name": "qwen3-8b.longer", "config": "qwen3-8b-1chip-s8k",
                               "traffic": "longer_docs", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "steps_in_window", "unit": "steps", "better": "higher",
                               "source": "host_clock", "layer": "train step",
                               "moves": "tokens_per_s", "workloads": ["qwen3-8b.longer"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = run.resolve("qwen3-8b.longer", root=tmp_path)
    assert cell.cfg["train"]["seq_len"] == 8192 and cell.mix["doc_lengths"]["mu"] == 7.0
    assert [m["name"] for m in cell.per_layer] == ["steps_in_window"]
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "step_s_p90", "peak_hbm_gib", "setup_s"} - {"step_s_p90"}
    with pytest.raises(KeyError):
        run.resolve("qwen3-8b.longer")  # the repository's own benchmark is unchanged


# A model type of its own, as a configuration PR adds it: an arch module
# whose `work` gives a count of its own, its reference, a configuration, and
# readers of a scope the program does not name today and of that count.
TOY_ARCH = """from chipbench import flops


def arch(cfg):
    raise NotImplementedError


def work(cfg, segment_ids):
    D, H, L, E = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_hidden_layers"], 6
    attn_flops, attn_bytes = flops.attention_work(segment_ids, H, H, 192, 128, L)
    tokens = flops.doc_tokens(segment_ids)
    moe_flops = 6 * tokens * E * 3 * D * cfg["moe_intermediate_size"] * L
    return {"flops": moe_flops + attn_flops, "attn_flops": attn_flops,
            "attn_bytes": attn_bytes, "moe_flops": moe_flops}
"""
MOE_MS = """from chipbench.layer_metrics import scope_ms


def read(ctx):
    return scope_ms(ctx, "moe")
"""
MOE_ROOFLINE = """from chipbench.layer_metrics import scope_ms


def read(ctx):
    ms = scope_ms(ctx, "moe")
    if ms is None:
        return None
    need = sum(s["moe_flops"] for s in ctx.steps) / ctx.peaks["bf16_flops_per_s"]
    return 100.0 * need / (1e-3 * ms * len(ctx.steps))
"""


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A copy of the benchmark's files that `import chipbench` finds first,
    as a run from a checkout would; the repository's own modules come back
    after the test."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for name in [m for m in sys.modules if m == "chipbench" or m.startswith("chipbench.")]:
        monkeypatch.delitem(sys.modules, name)
    monkeypatch.syspath_prepend(str(tmp_path))
    yield tmp_path
    for name in [m for m in sys.modules if m == "chipbench" or m.startswith("chipbench.")]:
        if (getattr(sys.modules[name], "__file__", None) or "").startswith(str(tmp_path)):
            del sys.modules[name]


def _add_toy_model(root, arch_text):
    base = root / "chipbench"
    (base / "archs" / "toymoe.py").write_text(arch_text)
    (base / "reference" / "toymoe.py").write_text("def make_step(cfg):\n    raise NotImplementedError\n")
    cfg = {"source": "https://example.org/toy-moe", "model_type": "toymoe", "runner": "spmd",
           "hidden_size": 2048, "num_attention_heads": 16, "num_hidden_layers": 5,
           "moe_intermediate_size": 1408, "vocab_size": 20480, "chips": 1, "reduced": {},
           "train": {"seq_len": 512, "global_batch": 2}}
    (base / "configs" / "toymoe.json").write_text(json.dumps(cfg))
    (base / "layer_metrics" / "moe_ms.py").write_text(MOE_MS)
    (base / "layer_metrics" / "moe_roofline.py").write_text(MOE_ROOFLINE)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toymoe", "source": cfg["source"],
                             "file": "chipbench/configs/toymoe.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toymoe.packed", "config": "toymoe", "traffic": "packed",
                               "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in ("mfu", "device_idle_share", "attn_roofline", "attn_ms"):
            m["workloads"].append("toymoe.packed")
    for name, unit, better in (("moe_ms", "ms", "lower"), ("moe_roofline", "%", "higher")):
        bench["per_layer"].append({"name": name, "unit": unit, "better": better,
                                   "source": "device_trace", "layer": "moe (models/moe.py)",
                                   "moves": "tokens_per_s", "workloads": ["toymoe.packed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_a_model_type_added_as_new_files_is_counted_and_read(checkout, monkeypatch):
    from types import SimpleNamespace

    from repro import tracing

    _add_toy_model(checkout, TOY_ARCH)
    from chipbench import run as run_here
    from chipbench import trace
    from chipbench.runners import common

    assert run_here.ROOT != ROOT
    monkeypatch.setattr(tracing, "SCOPES", tracing.SCOPES + ("moe",))
    cell = run_here.resolve("toymoe.packed", root=checkout)
    assert {m["name"] for m in cell.per_layer} == {
        "mfu", "device_idle_share", "attn_roofline", "attn_ms", "moe_ms", "moe_roofline"}
    traffic = common.traffic_for(cell.cfg, cell.mix, seed=2**31 + 11)
    steps = [{"t0": float(i), "t1": float(i + 1), **common.step_work(cell.cfg, traffic.batch(i))}
             for i in range(2)]
    assert all(s["moe_flops"] > 0 for s in steps)
    rec = {"devices": {"/device:TPU:0": [
        ["fusion.1", 0, 4_000_000, "jit(train_step)/while/body/transpose(jvp(moe))/dot_general"],
        ["fusion.2", 5_000_000, 1_000_000, "jit(train_step)/attn_proj/attn_core/exp"]]},
        "host": [["window", 0, 2_000_000_000]]}
    ctx = SimpleNamespace(cfg=cell.cfg, chips=1, steps=steps, trace=trace.reduce(rec),
                          setup_s=1.0, peak_bytes=0,
                          peaks={"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e12})
    got = run_here._read("layer_metrics", cell.per_layer, ctx)
    assert got["moe_ms"]["value"] == pytest.approx(2.0)
    moe = sum(s["moe_flops"] for s in steps) / 1e15
    assert got["moe_roofline"]["value"] == pytest.approx(100 * moe / 0.004)
    assert got["attn_ms"]["value"] == pytest.approx(0.5)
    assert set(got) == {m["name"] for m in cell.per_layer}
    # the repository's own benchmark is unchanged
    assert "toymoe.packed" not in {w["name"] for w in BENCH["workloads"]}


def test_a_model_type_whose_arch_module_counts_no_work_is_refused(checkout):
    _add_toy_model(checkout, TOY_ARCH.replace("def work(", "def _work("))
    from chipbench import run as run_here

    with pytest.raises(TypeError, match="no work"):
        run_here.resolve("toymoe.packed", root=checkout)


def test_the_names_text_is_this_trees_with_a_warm_compile_cache(tmp_path):
    """JAX's compile caches leave op_name out of their keys: with the same
    function compiled under another scope name first, a plain compile
    returns the other names; the runners' fresh_text returns this one's."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from chipbench.runners import common

    def step(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) @ x.T
        return jax.jit(f)

    x = jnp.ones((32, 32))
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
        jax.clear_caches()
        step("other_tree")(x).block_until_ready()  # fills the cache
        jax.clear_caches()
        here = step("attn_core")
        here(x).block_until_ready()  # read back from the cache
        stale = here.lower(x).compile().as_text()
        assert "other_tree" in stale and "attn_core" not in stale
        text = common.fresh_text(here, x)
        assert "attn_core" in text and "other_tree" not in text
        assert jax.config.jax_enable_compilation_cache
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def _run_cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen3-8b.packed", "--seed",
         "2147483649", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    out = _run_cli(ROOT)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "TPU" in out.stderr


def test_run_with_only_the_benchmarks_files_exits_nonzero(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _run_cli(tmp_path)
    assert out.returncode != 0 and "{" not in out.stdout


def test_readers_compute_their_metrics_from_a_window():
    from types import SimpleNamespace

    steps = [{"t0": 0.0, "t1": 3.5, "tokens": 6000, "flops": 2e13,
              "attn_flops": 1e11, "attn_bytes": 1e8},
             {"t0": 3.5, "t1": 20.5, "tokens": 7000, "flops": 2e13,
              "attn_flops": 1e11, "attn_bytes": 1e8},
             {"t0": 20.5, "t1": 23.0, "tokens": 5000, "flops": 2e13,
              "attn_flops": 1e11, "attn_bytes": 1e8}]
    ctx = SimpleNamespace(
        steps=steps, chips=4, peaks={"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12},
        setup_s=40.0,
        peak_bytes=3 * 2**30,
        trace={"window_s": 23.0, "busy_s": 2.3,
               "scope_s": {"attn_core": 0.3, "attn_proj": 0.15, "mlp": 0.9, "lm_head": 0.6,
                           "optimizer": 0.24, "unscoped": 0.06}})
    read = lambda kind, name: importlib.import_module(f"chipbench.{kind}.{name}").read(ctx)
    assert read("end_to_end", "tokens_per_s") == pytest.approx(18000 / 23.0)
    assert read("end_to_end", "step_s_p90") == pytest.approx(np_percentile([3.5, 17.0, 2.5], 90))
    assert read("end_to_end", "peak_hbm_gib") == 3.0
    assert read("end_to_end", "setup_s") == 40.0
    assert read("layer_metrics", "mfu") == pytest.approx(100 * 6e13 / (23.0 * 4 * 1e14))
    assert read("layer_metrics", "device_idle_share") == pytest.approx(90.0)
    assert read("layer_metrics", "attn_ms") == pytest.approx(100.0)
    # the larger of 3e11 FLOPs at 1e14/s (3 ms) and 3e8 bytes at 1e12/s (0.3 ms)
    assert read("layer_metrics", "attn_roofline") == pytest.approx(100 * 3e-3 / 0.3)
    for name, ms in [("attn_proj_ms", 50.0), ("mlp_ms", 300.0), ("lm_head_ms", 200.0),
                     ("optimizer_ms", 80.0), ("unscoped_ms", 20.0)]:
        assert read("layer_metrics", name) == pytest.approx(ms), name
    ctx.trace["scope_s"] = {"mlp": 0.9}  # a scope the trace holds no time under
    for name in ("attn_ms", "attn_roofline", "unscoped_ms"):
        assert read("layer_metrics", name) is None, name
    ctx.trace = None
    for name in ("device_idle_share", "attn_ms", "attn_roofline", "attn_proj_ms", "mlp_ms",
                 "lm_head_ms", "optimizer_ms", "unscoped_ms"):
        assert read("layer_metrics", name) is None, name


def np_percentile(values, q):
    import numpy as np

    return float(np.percentile(values, q))
