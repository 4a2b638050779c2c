"""Compile the main-path programs for a described TPU v5e chip at real widths.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that is
described, not attached, and refuses what the chip would refuse (block tiling,
fast-memory limits, programs that do not fit HBM). The topology is described
inside a fixture so that importing this file never loads the TPU library.
"""
import dataclasses
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.packed_flash_attn import packed_flash_attention
from repro.launch.train import spmd_step
from repro.parallel.sharding import NULL_POLICY
from repro.train.optimizer import optimizer_for
from repro.train.train_step import state_axes

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


def test_one_chip_train_step_fits_v5e(one_chip):
    """The cut chip_smoke.py trains: qwen3-8b at published widths, one layer
    (one whole period), a 1/8 vocabulary slice, seq 4096, batch 2 in two
    microbatches, AdamW with fp32 master state."""
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
    compiled = spmd_step(cfg, NULL_POLICY, opt, args).lower(state, batch).compile()
    m = compiled.memory_analysis()
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
