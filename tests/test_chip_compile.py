"""Compile the main-path programs for a described TPU v5e chip at real widths.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that is
described, not attached, and refuses what the chip would refuse (block tiling,
fast-memory limits, programs that do not fit HBM). The topology is described
inside a fixture so that importing this file never loads the TPU library.
"""
import dataclasses
import os
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import tracing
from repro.configs import get_arch, reduced
from repro.data.synth import SyntheticPackedDataset
from repro.kernels.packed_flash_attn import packed_flash_attention
from repro.launch.train import spmd_step
from repro.parallel.sharding import NULL_POLICY
from repro.train.optimizer import optimizer_for
from repro.train.train_step import init_train_state, state_axes

# What the compiler lets one v5e chip's program use (16 GiB HBM less runtime
# reservations), as its out-of-memory message reports it.
V5E_PROGRAM_BYTES = 15.75 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            described = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no description
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache off meanwhile
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield described
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("window", [None, 1024])
def test_packed_attention_compiles_at_qwen3_8b_widths(one_chip, window):
    B, S, H, K, dh = 2, 4096, 32, 8, 128
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    qkv = [sds((B, S, H, dh), jnp.bfloat16)] + [sds((B, S, K, dh), jnp.bfloat16)] * 2
    ids = [sds((B, S), jnp.int32)] * 4
    fn = jax.jit(lambda *a: packed_flash_attention(*a, window=window))
    compiled = fn.lower(*qkv, *ids).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def one_chip_step(one_chip):
    """The cut chip_smoke.py trains, compiled for one described chip: qwen3-8b
    at published widths, one layer (one whole period), a 1/8 vocabulary
    slice, seq 4096, batch 2 in two microbatches, AdamW with fp32 master
    state."""
    cfg = dataclasses.replace(get_arch("qwen3-8b"), n_layers=1, vocab_size=151936 // 8)
    opt = optimizer_for(cfg)
    args = SimpleNamespace(microbatches=2, seq_len=4096)
    params_s, opt_s, _ = state_axes(cfg, opt)
    place = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), t)
    state = place({"params": params_s, "opt": opt_s,
                   "step": jax.ShapeDtypeStruct((), jnp.int32)})
    batch = place({k: jax.ShapeDtypeStruct((2, args.seq_len), jnp.int32)
                   for k in ("tokens", "segment_ids", "positions", "labels")})
    return spmd_step(cfg, NULL_POLICY, opt, args).lower(state, batch).compile()


def test_one_chip_train_step_fits_v5e(one_chip_step):
    """The cut chip_smoke.py trains fits one chip's program memory."""
    m = one_chip_step.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used <= V5E_PROGRAM_BYTES, m


def test_pipeline_meshes_use_the_plan_devices_off_cpu(topo):
    """Off the CPU a stage mesh holds exactly the devices its plan names,
    and a plan naming more devices than exist raises instead of wrapping."""
    from repro.configs import reduced
    from repro.core.scheduler.plan import initial_plan
    from repro.engine.pipeline import PipelineEngine

    cfg = reduced(get_arch("qwen3-8b"), n_layers=2)
    eng = PipelineEngine(cfg, initial_plan(2, dp=1, pp=2, tp=2), devices=topo.devices)
    assert {k: [d.id for d in m.devices.flat] for k, m in eng.meshes.items()} == {
        (0, 0): [topo.devices[0].id, topo.devices[1].id],
        (0, 1): [topo.devices[2].id, topo.devices[3].id]}
    with pytest.raises(ValueError, match="only 4 exist"):
        eng.apply_plan(initial_plan(2, dp=2, pp=2, tp=2))


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def test_one_chip_train_step_trains_through_the_packed_kernel(one_chip_step):
    """Lowered for the TPU, the cut qwen3-8b step computes attention in the
    Pallas kernels, forward and backward, all under `attn_core`, and no
    attention loop is left."""
    text = one_chip_step.as_text()
    calls = [_OP_NAME.search(line).group(1) for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert {tracing.scope_of(n) for n in calls} == {"attn_core"}
    assert {n.split("/")[-2] for n in calls} == {"packed_attn_fwd", "packed_attn_dq",
                                                 "packed_attn_dkv"}
    loops = [_OP_NAME.search(line).group(1) for line in text.splitlines()
             if re.search(r"\swhile\(", line) and "op_name" in line]
    assert "attn_core" not in {tracing.scope_of(n) for n in loops}


def test_cpu_train_step_keeps_the_jnp_attention():
    """Lowered for the CPU, the train step keeps the chunked scan."""
    cfg = reduced(get_arch("qwen3-8b"), n_layers=2)
    state, _ = init_train_state(jax.random.PRNGKey(0), cfg, optimizer_for(cfg))
    batch = {k: jnp.asarray(v) for k, v in
             SyntheticPackedDataset(cfg, 64, 4, seed=0).batch_at(0).items()}
    text = spmd_step(cfg, NULL_POLICY, optimizer_for(cfg), SimpleNamespace(
        microbatches=2, seq_len=64)).lower(state, batch).compile().as_text()
    assert "attn_core/while" in text and "packed_attn" not in text


@pytest.mark.parametrize("tp", [1, 2])
def test_pipeline_stage_takes_the_kernel_unless_its_heads_are_split(topo, tp):
    """Lowered for the TPU under a pipeline stage's policy, attention takes
    the kernel on a one-chip stage, and keeps the dense path on a tp=2 stage,
    whose heads are split: GSPMD does not partition a kernel."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.scheduler.plan import initial_plan
    from repro.engine.pipeline import PipelineEngine
    from repro.models.attention import attention, init_attention
    from repro.parallel.sharding import split_annotations

    cfg = reduced(get_arch("qwen3-8b"), n_layers=2)
    eng = PipelineEngine(cfg, initial_plan(2, dp=1, pp=2, tp=tp), devices=topo.devices)
    pol, mesh = eng.policies[(0, 0)], eng.meshes[(0, 0)]
    assert mesh.devices.size == tp
    p, axes = split_annotations(jax.eval_shape(lambda k: init_attention(k, cfg),
                                               jax.random.PRNGKey(0)))
    rep = NamedSharding(mesh, PartitionSpec())
    p = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=pol.sharding_for(a, s.shape) or rep),
        axes, p, is_leaf=lambda x: isinstance(x, tuple))
    B, S = 2, 256
    x = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16, sharding=rep)
    ids = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=rep)

    def stage_attention(p, x, seg, pos):
        md = {"segment_ids": seg, "positions": pos, "abs_positions": pos, "causal": True,
              "flash_chunk": S // 2}  # as PipelineEngine passes it: the dense path
        return attention(cfg, cfg.layer_spec(0), p, x, md, pol)[0]

    text = jax.jit(stage_attention).lower(p, x, ids, ids).compile().as_text()
    assert ("packed_attn_fwd" in text) == (tp == 1)
    assert ("attn_core/bqhd,bkhd->bhqk/dot_general" in text) == (tp == 2)
