"""The program's names in a profiler trace (repro.tracing): every matmul of
the compiled train step under a layer scope, the attention core named on
every path, the innermost-scope rule, and the host spans of the SPMD loop
and of PipelineEngine as a captured profile holds them."""
import argparse
import glob
import os
import re
import subprocess
import sys
import textwrap
from collections import defaultdict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import get_arch, reduced
from repro.data.synth import SyntheticPackedDataset
from repro.kernels.packed_flash_attn import BLOCK_K, BLOCK_Q, skipped_block_fraction
from repro.launch.train import run_spmd, spmd_step
from repro.models.attention import attention, init_attention
from repro.parallel.sharding import NULL_POLICY, split_annotations
from repro.train.optimizer import optimizer_for
from repro.train.train_step import init_train_state

CFG = reduced(get_arch("qwen3-8b"))
REPO = Path(__file__).resolve().parents[1]
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _op_names(fn, *args):
    """The op_name of every instruction of fn's compiled program that the
    traced function made (parameters and reducer bodies carry no jit())."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return [n for n in _OP_NAME.findall(text) if n.startswith("jit(")]


@pytest.mark.parametrize("name, scope", [
    ("jit(train_step)/while/body/checkpoint/attn_proj/attn_core/bqhd,bkhd->bhqk/dot_general",
     "attn_core"),
    ("jit(train_step)/transpose(jvp(attn_proj))/bsd,dhk->bshk/dot_general", "attn_proj"),
    ("jit(train_step)/transpose(jvp(mlp))/bsf,fd->bsd/dot_general", "mlp"),
    ("jit(train_step)/jvp(lm_head)/bsd,dv->bsv/dot_general", "lm_head"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(train_step)/state['params']['layers'][0]['ffn']['w_gate']", None),
    ("jit(train_step)/state['params']['layers'][0]['mlp']", None),
    ("jit(train_step)/mlp_out/add", None),
    ("jit(train_step)/while/body/dynamic_slice", None),
    ("", None),
])
def test_scope_of_takes_the_innermost_whole_component(name, scope):
    assert tracing.scope_of(name) == scope


def test_scopes_split_device_time_with_no_overlap():
    # a record of (op_name, seconds): nested scopes, wrapped scopes and a
    # parameter path that holds the names 'ffn' and 'mlp'
    record = [
        ("jit(step)/attn_proj/attn_core/exp", 4.0),
        ("jit(step)/attn_proj/bsd,dhk->bshk/dot_general", 2.0),
        ("jit(step)/transpose(jvp(attn_proj))/attn_core/dot_general", 3.0),
        ("jit(step)/jvp(mlp)/dot_general", 5.0),
        ("jit(step)/state['params']['ffn']['mlp']", 1.5),
        ("jit(step)/lm_head/log_softmax", 0.5),
        ("jit(step)/optimizer/optimizer/mul", 0.25),
        ("", 0.75),
    ]
    by_scope, unscoped = defaultdict(float), 0.0
    for name, seconds in record:
        scope = tracing.scope_of(name)
        if scope is None:
            unscoped += seconds
        else:
            by_scope[scope] += seconds
    assert dict(by_scope) == {"attn_core": 7.0, "attn_proj": 2.0, "mlp": 5.0,
                              "lm_head": 0.5, "optimizer": 0.25}
    assert unscoped == 1.5 + 0.75
    assert sum(by_scope.values()) + unscoped == sum(s for _, s in record)


def test_scope_and_span_names_are_checked():
    with pytest.raises(ValueError, match="unknown scope"):
        tracing.scope("ffn")
    with pytest.raises(ValueError, match="unknown span"):
        tracing.span("window")
    assert not set(tracing.SCOPES) & set(tracing.SPANS)


def test_every_matmul_of_the_train_step_falls_under_a_layer_scope():
    """The SPMD step at a CPU size (seq 64: the chunked attention path),
    forward, remat and backward: each dot_general is under a layer's scope,
    and Adam's and the global norm's square roots under `optimizer`."""
    opt = optimizer_for(CFG)
    state, _ = init_train_state(jax.random.PRNGKey(0), CFG, opt)
    batch = {k: jnp.asarray(v) for k, v in
             SyntheticPackedDataset(CFG, 64, 4, seed=0).batch_at(0).items()}
    step = spmd_step(CFG, NULL_POLICY, opt, argparse.Namespace(microbatches=2, seq_len=64))
    text = step.lower(state, batch).compile().as_text()
    names = _OP_NAME.findall(text)
    dots = [n for n in names if n.endswith("/dot_general")]
    assert dots
    assert {n for n in dots if tracing.scope_of(n) not in
            ("attn_core", "attn_proj", "mlp", "lm_head")} == set()
    assert {tracing.scope_of(n) for n in dots} == {"attn_core", "attn_proj", "mlp", "lm_head"}
    roots = [n for n in names if n.endswith("/sqrt")]
    assert roots and {tracing.scope_of(n) for n in roots} == {"optimizer"}
    assert "attn_core/while" in text  # the chunked path's scan, named whole


def _attention_inputs():
    B, S = 2, 64
    p, _ = split_annotations(init_attention(jax.random.PRNGKey(0), CFG))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, CFG.d_model), jnp.float32)
    pos = jnp.concatenate([jnp.arange(40), jnp.arange(24)])
    md = {"segment_ids": jnp.broadcast_to(1 + (jnp.arange(S) >= 40), (B, S)).astype(jnp.int32),
          "positions": jnp.broadcast_to(pos, (B, S)).astype(jnp.int32),
          "abs_positions": jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)),
          "causal": True}
    return p, x, md


PALLAS = {"use_pallas_kernel": True, "kernel_block_q": 16, "kernel_block_k": 16}


@pytest.mark.parametrize("path, extra, marker", [
    ("dense", {"flash_chunk": 64}, "attn_proj/attn_core/bqhd,bkhd->bhqk/dot_general"),
    ("chunked", {"flash_chunk": 16}, "attn_proj/attn_core/while/body/"),
    ("pallas", PALLAS, "attn_proj/attn_core/jit(packed_flash_attention)/"),
    ("pallas backward dq", PALLAS,
     "transpose(jvp(attn_proj))/attn_core/jit(packed_flash_attention)/packed_attn_dq/"),
    ("pallas backward dkv", PALLAS,
     "transpose(jvp(attn_proj))/attn_core/jit(packed_flash_attention)/packed_attn_dkv/"),
])
def test_attn_core_names_the_core_on_every_path(path, extra, marker):
    p, x, md = _attention_inputs()
    md = {**md, **extra}
    layer = lambda p, x: attention(CFG, CFG.layer_spec(0), p, x, md, NULL_POLICY)[0]
    if "backward" in path:  # the layer's vjp: forward, then both backward kernels
        ct = jnp.ones(x.shape, x.dtype)
        names = _op_names(lambda p, x, ct: jax.vjp(layer, p, x)[1](ct), p, x, ct)
    else:
        names = _op_names(layer, p, x)
    # the switch on the platform, a cond of one branch per platform, puts
    # its branch between the scopes on the paths it chooses between
    names = [re.sub(r"cond/branch_\d+_fun/", "", n) for n in names]
    assert any(marker in n for n in names), path
    assert {tracing.scope_of(n) for n in names} == {"attn_proj", "attn_core"}
    projections = [n for n in names if re.search(r"/(bsd,dhk->bshk|bsd,dkh->bskh|bshk,hkd->bsd)/", n)]
    assert projections and {tracing.scope_of(n) for n in projections} == {"attn_proj"}
    core = [n for n in names if "bhqk" in n or "packed_flash_attention" in n]
    assert core and {tracing.scope_of(n) for n in core} == {"attn_core"}


def _host_events(directory):
    """[(name, start_ns, end_ns, stats)] of the host planes of the profile
    captured under `directory`, in start order."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == tracing.STEP or e.name in tracing.SPANS:
                        start = int(e.start_ns)
                        out.append((e.name, start, start + int(e.duration_ns), dict(e.stats)))
    return sorted(out, key=lambda e: e[1])


def _by_step(events):
    """{step_num: [the spans inside that step's annotation]}."""
    steps = [e for e in events if e[0] == tracing.STEP]
    out = {}
    for _, lo, hi, stats in steps:
        out[stats["step_num"]] = [e for e in events
                                  if e[0] != tracing.STEP and lo <= e[1] and e[2] <= hi]
    return out


def test_run_spmd_profile_holds_each_step_and_its_spans(tmp_path):
    args = argparse.Namespace(
        lr=1e-3, tp=1, seed=0, microbatches=2, seq_len=32, batch=4, steps=4,
        ckpt_dir=str(tmp_path / "ckpt"), ckpt_interval=1, resume=False,
        profile_dir=str(tmp_path / "profile"))
    run_spmd(CFG, args)
    steps = _by_step(_host_events(args.profile_dir))
    assert sorted(steps) == [2, 3]  # the steps after the first two
    for spans in steps.values():
        assert [e[0] for e in spans] == ["batch", "dispatch", "wait_state", "detect",
                                         "checkpoint"]
        stats = {e[0]: e[3] for e in spans}
        assert stats["batch"]["bytes"] == 4 * 4 * 32 * 4  # four int32 fields, (4, 32)
        assert stats["checkpoint"]["bytes"] > 0  # saved: interval 1


def test_detect_span_counts_the_attention_tiles_the_kernel_runs(tmp_path):
    """The `detect` span's attn_tiles / attn_tiles_run, from the host batch at
    the kernel's block size: their ratio is 1 - skipped_block_fraction."""
    args = argparse.Namespace(
        lr=1e-3, tp=1, seed=0, microbatches=2, seq_len=1024, batch=2, steps=3,
        ckpt_dir=None, ckpt_interval=1, resume=False, profile_dir=str(tmp_path / "profile"))
    run_spmd(CFG, args)
    steps = _by_step(_host_events(args.profile_dir))
    assert sorted(steps) == [2]
    stats, = [e[3] for e in steps[2] if e[0] == "detect"]
    seg = SyntheticPackedDataset(CFG, args.seq_len, args.batch, seed=args.seed).batch_at(2)[
        "segment_ids"]
    pos = np.broadcast_to(np.arange(args.seq_len, dtype=np.int32), seg.shape)
    assert stats["attn_tiles"] == args.batch * (args.seq_len // BLOCK_Q) * (args.seq_len // BLOCK_K)
    skipped = skipped_block_fraction(seg, pos, BLOCK_Q, BLOCK_K)
    assert 0 < skipped < 1
    assert stats["attn_tiles_run"] / stats["attn_tiles"] == pytest.approx(1 - skipped, abs=1e-12)


PIPELINE = textwrap.dedent("""
    import sys
    import jax
    from repro.configs import get_arch, reduced
    from repro.core.scheduler.plan import initial_plan
    from repro.data.synth import SyntheticPackedDataset
    from repro.engine.pipeline import PipelineEngine
    from repro.train.optimizer import make_optimizer

    assert len(jax.devices()) == 4
    cfg = reduced(get_arch("qwen3-8b"), n_layers=2)
    ds = SyntheticPackedDataset(cfg, 32, 4, seed=0)
    eng = PipelineEngine(cfg, initial_plan(2, dp=1, pp=2, tp=2, microbatches=2),
                         optimizer=make_optimizer("adamw"), seed=0)
    jax.profiler.start_trace(sys.argv[1])
    for it in range(2):
        eng.run_iteration({k: jax.numpy.asarray(v) for k, v in ds.batch_at(it).items()})
    eng.apply_plan(eng.plan)
    jax.profiler.stop_trace()
""")


def test_pipeline_iteration_profile_holds_its_chunks_and_transfers(tmp_path):
    """dp1 x pp2 x tp2 on four virtual CPU devices, two iterations and a
    re-plan under one capture."""
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", PIPELINE, str(tmp_path)], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    events = _host_events(str(tmp_path))
    steps = _by_step(events)
    assert sorted(steps) == [0, 1]
    for num, spans in steps.items():
        names = [e[0] for e in spans]
        assert names[0] == "stage_params" and names[-2:] == ["loss_sync", "apply_grads"]
        assert names.count("F") == names.count("B") == 4  # 2 stages x 2 microbatches
        assert names.count("send") == 4  # activations forward, gradients back
        for name, _, _, stats in spans:
            if name in ("stage_params", "send", "apply_grads"):
                assert stats["bytes"] > 0, name
        chunks = {(e[0], e[3]["replica"], e[3]["stage"], e[3]["mb"]): e
                  for e in spans if e[0] in ("F", "B")}
        for (kind, r, s, m), e in chunks.items():
            if kind == "B":
                assert chunks[("F", r, s, m)][2] <= e[1]  # F ends before its B starts
            # a stage function is built, and compiles, on its first call only
            assert e[3]["built"] == int(num == 0 and m == 0), (kind, s, m)
    assert [e[0] for e in events if e[0] == "apply_plan"] == ["apply_plan"]
