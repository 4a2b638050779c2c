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
    importlib.import_module(f"chipbench.archs.{cell.cfg['model_type']}").arch
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
        trace={"window_s": 23.0, "busy_s": 2.3, "scope_s": {"attn_core": 0.3}})
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
    ctx.trace = None
    for kind, name in [("layer_metrics", "device_idle_share"), ("layer_metrics", "attn_ms"),
                       ("layer_metrics", "attn_roofline")]:
        assert read(kind, name) is None, name


def np_percentile(values, q):
    import numpy as np

    return float(np.percentile(values, q))
