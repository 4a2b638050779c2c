"""Smoke run of the training path on TPU v5e, through the trainer's own entries.

    python chip_smoke.py               # one chip: kernel + SPMD trainer
    python chip_smoke.py --four-chips  # four chips (2x2): PipelineEngine only

One chip (the default):
  * kernel: the Pallas packed flash attention, compiled natively, at qwen3-8b
    attention widths on a heavy-tailed packed batch, against the float32
    reference at highest matmul precision;
  * trainer: `repro.launch.train.run_spmd`, 5 steps of qwen3-8b at published
    widths cut to one layer (one whole period) and a 1/8 vocabulary slice;
    the step-0 loss is checked against the same loss in float32.
Four chips: `repro.launch.train.run_pipeline` with dp1 x pp2 x tp2 over two
qwen3-8b layers at published widths, a fail-stop of one TP member at step 3,
and the step-0 loss checked against the same forward loss on one chip.

Weights and data are random, made from SEED. The last line of stdout is one
JSON object naming the device; any failed phase raises, so the exit code is
non-zero and that line is never printed. With no TPU it exits non-zero first.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
QWEN3_VOCAB = 151936
SEQ = 4096  # qwen3-8b's training sequence (the train_4k shape)

# Kernel vs float32 reference. The kernel's output is bf16, so each element
# carries up to 2^-8 relative rounding; on top, the MXU may take its f32
# operands in bf16 passes. 2e-2 covers both with room and matches the bf16
# tolerance of the interpret-mode tests (tests/test_kernels.py).
KERNEL_ATOL = KERNEL_RTOL = 2e-2
# Step-0 losses (~ln V ~ 10) against a reference on the same params and batch.
# One bf16 ulp at that magnitude is 0.0625; the loss averages ~8k per-token
# terms whose bf16 rounding is independent, so a correct path lands far
# inside 2e-2 and a wrong mask, layout or sharding lands far outside.
LOSS_ATOL = 2e-2


def check(ok, what):
    """`assert` that survives `python -O`."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def kernel_phase():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch
    from repro.data.synth import SyntheticPackedDataset
    from repro.kernels.packed_flash_attn import packed_flash_attention, skipped_block_fraction
    from repro.kernels.ref import packed_attention_ref

    cfg = get_arch("qwen3-8b")
    B, H, K, dh = 2, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    seg = jnp.asarray(SyntheticPackedDataset(cfg, SEQ, B, seed=SEED).batch_at(0)["segment_ids"])
    pos = jnp.broadcast_to(jnp.arange(SEQ, dtype=jnp.int32), (B, SEQ))  # as the model passes
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q = jax.random.normal(kq, (B, SEQ, H, dh), jnp.bfloat16)
    k = jax.random.normal(kk, (B, SEQ, K, dh), jnp.bfloat16)
    v = jax.random.normal(kv, (B, SEQ, K, dh), jnp.bfloat16)
    ids = (seg, seg, pos, pos)

    fn = jax.jit(functools.partial(packed_flash_attention, causal=True, interpret=False))
    t0 = time.perf_counter()
    compiled = fn.lower(q, k, v, *ids).compile()
    compile_s = time.perf_counter() - t0
    check("tpu_custom_call" in compiled.as_text(), "kernel lowered to a TPU custom call")
    out = compiled(q, k, v, *ids).block_until_ready()
    call_s = []
    for _ in range(3):
        t0 = time.perf_counter()
        compiled(q, k, v, *ids).block_until_ready()
        call_s.append(time.perf_counter() - t0)

    # float32 reference, 8 query heads (2 KV heads) at a time to bound memory
    hg = 8
    kg = hg * K // H
    ref_fn = jax.jit(functools.partial(packed_attention_ref, causal=True))
    f32 = lambda x: x.astype(jnp.float32)
    worst_abs, worst_ratio = 0.0, 0.0
    for b in range(B):
        for g in range(H // hg):
            ref = ref_fn(f32(q[b:b + 1, :, g * hg:(g + 1) * hg]),
                         f32(k[b:b + 1, :, g * kg:(g + 1) * kg]),
                         f32(v[b:b + 1, :, g * kg:(g + 1) * kg]),
                         *(x[b:b + 1] for x in ids))
            ref = np.asarray(ref)
            got = np.asarray(out[b:b + 1, :, g * hg:(g + 1) * hg], np.float32)
            err = np.abs(got - ref)
            worst_abs = max(worst_abs, float(err.max()))
            worst_ratio = max(worst_ratio,
                              float((err / (KERNEL_ATOL + KERNEL_RTOL * np.abs(ref))).max()))
    print(f"[kernel] B={B} S={SEQ} H={H} K={K} dh={dh} bf16: compile {compile_s:.2f} s, "
          f"calls {[round(s, 6) for s in call_s]} s, tiles skipped "
          f"{skipped_block_fraction(seg, pos, 128, 128):.4f}")
    print(f"[kernel] max |kernel - f32 ref| = {worst_abs:.6g}; worst err / "
          f"(atol {KERNEL_ATOL} + rtol {KERNEL_RTOL} * |ref|) = {worst_ratio:.4f}")
    check(worst_ratio <= 1.0, "kernel within tolerance of the float32 reference")


def _microbatches(batch, n):
    import jax.numpy as jnp
    rows = len(batch["tokens"]) // n
    return [{k: jnp.asarray(v[m * rows:(m + 1) * rows]) for k, v in batch.items()}
            for m in range(n)]


def trainer_phase():
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.data.synth import SyntheticPackedDataset
    from repro.launch.train import run_spmd
    from repro.models.model import loss_fn, stacked_init
    from repro.parallel.sharding import NULL_POLICY, split_annotations

    cfg = dataclasses.replace(get_arch("qwen3-8b"), n_layers=1, vocab_size=QWEN3_VOCAB // 8)
    args = argparse.Namespace(
        seed=SEED, lr=3e-4, tp=1, microbatches=2, seq_len=SEQ, batch=2, steps=5,
        ckpt_dir=None, ckpt_interval=10, resume=False, profile_dir=None)
    print(f"[trainer] qwen3-8b widths, {cfg.n_layers} layer, vocab {cfg.vocab_size} "
          f"(padded {cfg.padded_vocab}), {cfg.param_count() / 1e6:.1f}M params, "
          f"seq {args.seq_len}, batch {args.batch} in {args.microbatches} microbatches")
    res = run_spmd(cfg, args)
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"[trainer] compile {res['compile_s']:.2f} s; step seconds (post-compile, "
          f"block_until_ready on the state) {res['times']}")
    print(f"[trainer] peak_bytes_in_use {peak}")
    print(f"[trainer] losses {res['losses']}")
    check(all(math.isfinite(l) for l in res["losses"]), "trainer losses finite")

    # step-0 loss in float32 at highest precision, same params, batch and path
    params, _ = split_annotations(stacked_init(jax.random.PRNGKey(SEED), cfg))
    batch = SyntheticPackedDataset(cfg, args.seq_len, args.batch, seed=SEED).batch_at(0)
    ref_loss = jax.jit(lambda p, mb: loss_fn(
        cfg, p, mb, NULL_POLICY, remat=False, flash_chunk=max(args.seq_len // 4, 16),
        compute_dtype=jnp.float32)[0])
    with jax.default_matmul_precision("highest"):
        ref = [float(ref_loss(params, mb)) for mb in _microbatches(batch, args.microbatches)]
    ref = sum(ref) / len(ref)
    diff = abs(res["losses"][0] - ref)
    print(f"[trainer] step-0 loss {res['losses'][0]:.6f} vs float32 {ref:.6f}: "
          f"|diff| {diff:.3g} (atol {LOSS_ATOL})")
    check(diff <= LOSS_ATOL, "step-0 loss agrees with the float32 loss")


def pipeline_phase():
    import jax

    from repro.configs import get_arch
    from repro.data.synth import SyntheticPackedDataset
    from repro.launch.train import run_pipeline
    from repro.models.model import loss_fn, stacked_init
    from repro.parallel.sharding import NULL_POLICY, split_annotations

    devices = jax.devices()
    check(len(devices) == 4, f"four chips, found {len(devices)}")
    # chip 0 holds the fp32 master params and both Adam moments of the whole
    # model; 1/6 is the largest simple vocabulary slice whose compiled
    # programs leave it >1 GiB of headroom (PERF.md, Layers)
    cfg = dataclasses.replace(get_arch("qwen3-8b"), n_layers=2, vocab_size=QWEN3_VOCAB // 6)
    args = argparse.Namespace(
        dp=1, pp=2, tp=2, microbatches=2, seq_len=SEQ, batch=2, steps=5, lr=3e-4,
        seed=SEED, ckpt_dir=None, ckpt_interval=10, resume=False,
        inject_failstop="3:1", inject_failslow=None, profile_dir=None)
    print(f"[pipeline] qwen3-8b widths, {cfg.n_layers} layers, vocab {cfg.vocab_size} "
          f"(padded {cfg.padded_vocab}), {cfg.param_count() / 1e6:.1f}M params, "
          f"dp1 pp2 tp2, seq {args.seq_len}, batch {args.batch}, fail-stop {args.inject_failstop}")
    t0 = time.perf_counter()
    res = run_pipeline(cfg, args)
    wall = time.perf_counter() - t0
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    print(f"[pipeline] {args.steps} steps in {wall:.2f} s wall (compiles included)")
    print(f"[pipeline] peak_bytes_in_use per device {peaks}")
    print(f"[pipeline] losses {res['losses']}, re-planned at steps {res['reconfigs']}")
    print(f"[pipeline] placements {json.dumps(res['placements'])}")
    check(all(math.isfinite(l) for l in res["losses"]), "pipeline losses finite")
    check(res["reconfigs"] == [3], "re-planned at step 3")
    for pl in res["placements"]:
        used = []
        for st in pl["stages"]:
            check(st["mesh"] == [devices[d].id for d in st["plan"]],
                  f"stage {st['stage']} mesh holds the plan's devices: {st}")
            used += st["mesh"]
        check(len(used) == len(set(used)), f"distinct devices at step {pl['step']}: {used}")
    check([len({d for st in pl["stages"] for d in st["mesh"]}) for pl in res["placements"]]
          == [4, 3], "four chips, then three after the fail-stop")

    # the same forward loss on one chip: token-weighted over the microbatches
    params, _ = split_annotations(stacked_init(jax.random.PRNGKey(SEED), cfg))
    batch = SyntheticPackedDataset(cfg, args.seq_len, args.batch, seed=SEED).batch_at(0)
    fwd = jax.jit(lambda p, mb: loss_fn(
        cfg, p, mb, NULL_POLICY, use_scan=False, remat=False,
        flash_chunk=args.seq_len // 2)[1])
    nll, ntok = 0.0, 0.0
    for mb in _microbatches(batch, args.microbatches):
        aux = fwd(params, mb)
        nll += float(aux["loss"]) * float(aux["ntokens"])
        ntok += float(aux["ntokens"])
    ref = nll / ntok
    diff = abs(res["losses"][0] - ref)
    print(f"[pipeline] step-0 loss {res['losses'][0]:.6f} vs one chip {ref:.6f}: "
          f"|diff| {diff:.3g} (atol {LOSS_ATOL})")
    check(diff <= LOSS_ATOL, "step-0 pipeline loss agrees with the one-chip loss")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip PipelineEngine path and its comparison")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found {device}", file=sys.stderr)
        return 1
    print(f"[device] {device}")

    from repro.launch.train import use_compile_cache

    use_compile_cache()
    cache = (jax.config.jax_compilation_cache_dir
             if jax.config.jax_enable_compilation_cache else "off (several TPU chips)")
    print(f"[device] persistent compile cache: {cache}")
    if args.four_chips:
        pipeline_phase()
    else:
        kernel_phase()
        trainer_phase()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
