"""End-to-end fault-tolerant training driver.

Two execution modes share the data pipeline, optimizer, checkpointing, and
the ResiHP stack:

  * spmd     — single-mesh pjit training (the production path the dry-run
               compiles at (16,16)/(2,16,16); here it runs on the host's
               devices). Iteration times + pack stats stream to the Detector.
  * pipeline — the ResiHP runtime: ParallelPlan executed by PipelineEngine
               with per-stage meshes; failure injection triggers the full
               detect -> adapt -> recover -> resume path in-process.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
      --steps 40 --mode spmd
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
      --mode pipeline --dp 2 --pp 2 --tp 1 --steps 30 \
      --inject-failstop 10:5 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import os

if os.environ.get("REPRO_HOST_DEVICES"):  # must precede any jax import
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + os.environ["REPRO_HOST_DEVICES"]
    ).strip()

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import tracing
from repro.checkpoint import CheckpointManager
from repro.configs import get_arch, reduced as reduce_cfg
from repro.core.detector.changepoint import CusumDetector
from repro.core.detector.detector import Detector
from repro.core.detector.heartbeat import HeartbeatMonitor
from repro.core.detector.predictor import MicroBatchTimePredictor
from repro.core.recovery import recover_state, transfer_plan
from repro.core.resihp import ResiHPController
from repro.core.scheduler.plan import initial_plan
from repro.core.scheduler.repartition import costs_for_arch
from repro.core.scheduler.scheduler import Scheduler
from repro.data.packing import pack_stats
from repro.data.synth import SyntheticPackedDataset
from repro.engine.pipeline import PipelineEngine
from repro.kernels.packed_flash_attn import tile_counts
from repro.parallel.sharding import NULL_POLICY, policy_for_mesh
from repro.train.optimizer import optimizer_for
from repro.train.train_step import build_train_step, init_train_state, sharding_for_state


REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache():
    """Keep compiled programs across processes. JAX reads
    JAX_COMPILATION_CACHE_DIR itself; without it the cache lives at a fixed
    path inside the checkout, so the next run finds it again.

    A program that spans several TPU chips halts the chip when it is read
    back from the cache (v5e, jax 0.9.0 / libtpu 0.0.34: a psum over two
    chips runs when compiled and halts when loaded), so a process that sees
    more than one TPU chip keeps the cache off."""
    devices = jax.devices()
    if devices[0].platform == "tpu" and len(devices) > 1:
        jax.config.update("jax_enable_compilation_cache", False)
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_ROOT / ".jax_cache"))


def _parse_inject(spec):
    """'step:device[,step:device...]' -> [(step, device)]."""
    out = []
    if spec:
        for part in spec.split(","):
            s, d = part.split(":")
            out.append((int(s), int(d)))
    return out


# ---------------------------------------------------------------- spmd mode
def run_spmd(cfg, args):
    n_dev = len(jax.devices())
    opt = optimizer_for(cfg, lr=args.lr)
    if n_dev > 1:
        dp = max(1, n_dev // args.tp)
        mesh = jax.make_mesh((dp, args.tp), ("data", "model"))
        policy = policy_for_mesh(mesh)
    else:
        mesh, policy = None, NULL_POLICY

    state, axes = init_train_state(jax.random.PRNGKey(args.seed), cfg, opt)
    if policy.mesh is not None:
        state_sh, _, _ = sharding_for_state(policy, cfg, opt)
        state = jax.tree.map(
            lambda x, s: jax.device_put(x, s) if s is not None else x, state, state_sh)
    step_fn = spmd_step(cfg, policy, opt, args)

    ckpt = CheckpointManager(args.ckpt_dir, interval=args.ckpt_interval) if args.ckpt_dir else None
    start = 0
    if ckpt and ckpt.has_checkpoint() and args.resume:
        state, start, extra = ckpt.restore_latest(target=state)
        print(f"[train] resumed from step {start}")

    ds = SyntheticPackedDataset(cfg, args.seq_len, args.batch, seed=args.seed)
    pred = MicroBatchTimePredictor()
    detector = Detector(
        healthy_time_fn=lambda w: pred.predict(*w) if pred.fitted else float("inf"),
        validate_fn=lambda it: [],
        heartbeat=HeartbeatMonitor(),
        changepoint_factory=lambda: CusumDetector(warmup=8),
    )
    losses, times, compile_s = [], [], None
    with tracing.profile(args.profile_dir) as capture:
        for it in range(start, args.steps):
            capture(it - start)
            with tracing.step(it):
                host = ds.batch_at(it)
                with tracing.span("batch", bytes=tracing.nbytes(host)):
                    batch = {k: jnp.asarray(v) for k, v in host.items()}
                if compile_s is None:
                    t0 = time.perf_counter()
                    step_fn = step_fn.lower(state, batch).compile()
                    compile_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                with tracing.span("dispatch"):
                    state, metrics = step_fn(state, batch)
                with tracing.span("wait_state"):
                    jax.block_until_ready(state)
                dt = time.perf_counter() - t0
                loss = float(metrics["loss"])
                with tracing.span("detect") as detect:
                    tiles, tiles_run = tile_counts(host["segment_ids"])
                    detect.set_metadata(attn_tiles=tiles, attn_tiles_run=tiles_run)
                    stats = pack_stats(host["segment_ids"])
                    n, l2 = sum(s[0] for s in stats), sum(s[1] for s in stats)
                    if it - start >= 2:  # skip warm-up iterations
                        pred.observe(n, l2, dt)
                        if len(pred._obs) >= 4 and not pred.fitted:
                            pred.fit()
                        detector.observe_iteration(it, dt, (n, l2))
                losses.append(loss)
                times.append(dt)
                if ckpt:
                    _checkpoint(ckpt, state, it + 1, loss)
            if it % max(args.steps // 10, 1) == 0 or it == args.steps - 1:
                print(f"[train] step {it} loss {loss:.4f} {dt*1e3:.0f} ms")
    return {"losses": losses, "times": times, "compile_s": compile_s,
            "detector": detector.stats.as_dict()}


def _checkpoint(ckpt, state, step, loss):
    """Save every `ckpt.interval` steps, under the span `checkpoint`."""
    with tracing.span("checkpoint") as saved:
        if ckpt.maybe_save(state, step, extra={"loss": loss}):
            saved.set_metadata(bytes=tracing.nbytes(state))


def spmd_step(cfg, policy, opt, args):
    """The jitted SPMD train step (also compiled for a described chip by
    tests/test_chip_compile.py)."""
    return jax.jit(build_train_step(
        cfg, policy, opt, microbatches=args.microbatches, remat=True,
        flash_chunk=max(args.seq_len // 4, 16)))


# ------------------------------------------------------------ pipeline mode
def run_pipeline(cfg, args):
    opt = optimizer_for(cfg, lr=args.lr)
    plan = initial_plan(cfg.n_layers, args.dp, args.pp, args.tp,
                        microbatches=args.microbatches)
    layer_costs = costs_for_arch(cfg, args.seq_len)
    scheduler = Scheduler(layer_costs=layer_costs, k_min=1, delta=1)
    hb = HeartbeatMonitor()
    node_devs = {}
    for d in plan.devices:
        node_devs.setdefault(d // 8, []).append(d)
    for n, devs in node_devs.items():
        hb.register_node(n, devs)
    detector = Detector(healthy_time_fn=lambda w: float("inf"),
                        validate_fn=lambda it: [], heartbeat=hb)
    controller = ResiHPController(
        scheduler=scheduler, detector=detector, plan=plan,
        speeds={d: 1.0 for d in plan.devices})

    engine = PipelineEngine(cfg, plan, optimizer=opt, seed=args.seed)
    ckpt = CheckpointManager(args.ckpt_dir, interval=args.ckpt_interval) if args.ckpt_dir else None
    ds = SyntheticPackedDataset(cfg, args.seq_len, args.batch, seed=args.seed)
    injections = dict(_parse_inject(args.inject_failstop))
    slow_inj = {}
    if args.inject_failslow:
        for part in args.inject_failslow.split(","):
            s, rest = part.split(":")
            d, f = rest.split("@")
            slow_inj[int(s)] = (int(d), float(f))

    start = 0
    if ckpt and ckpt.has_checkpoint() and args.resume:
        full, start, _ = ckpt.restore_latest(
            target={"params": engine.params_full, "opt": engine.opt_state,
                    "step": engine.step})
        engine.params_full, engine.opt_state = full["params"], full["opt"]
        engine.step = int(full["step"]) if not isinstance(full["step"], int) else full["step"]
        print(f"[train] resumed from step {start}")

    def placement(it):  # plan device ids vs the devices each stage mesh holds
        return {"step": it, "stages": [
            {"stage": [r, s], "plan": list(engine.plan.stage(r, s).devices),
             "mesh": [d.id for d in mesh.devices.flat]}
            for (r, s), mesh in engine.meshes.items()]}

    losses = []
    reconfigs = []
    placements = [placement(start)]
    with tracing.profile(args.profile_dir) as capture:
        for it in range(start, args.steps):
            capture(it - start)
            now = float(it)
            from repro.core.detector.detector import FailureReport

            if it in injections:
                dev = injections[it]
                print(f"[inject] fail-stop device {dev} at step {it}")
                controller.speeds[dev] = 0.0
                controller.pending.append(FailureReport("fail-stop", (dev,), it, now))
            if it in slow_inj:
                dev, f = slow_inj[it]
                print(f"[inject] fail-slow device {dev} -> {f} at step {it}")
                controller.speeds[dev] = f
                controller.pending.append(FailureReport("fail-slow", ((dev, f),), it, now))

            adaptation = controller.adapt(now)
            if adaptation is not None:
                old_plan = engine.plan
                print(f"[adapt] {adaptation.plan.summary()}")
                for note in adaptation.notes:
                    print(f"        {note}")
                tp_ = transfer_plan(cfg, old_plan, adaptation.plan,
                                    dead_stages=adaptation.dead_stages)
                print(f"[recover] {len(tp_.moves)} layer moves, "
                      f"{tp_.total_bytes/1e6:.1f} MB, est {tp_.seconds():.2f}s on IB")
                if tp_.restore_required:
                    if ckpt is None or not ckpt.has_checkpoint():
                        raise RuntimeError("stage lost all replicas and no checkpoint")
                    full, step0, _ = ckpt.restore_latest(
                        target={"params": engine.params_full, "opt": engine.opt_state,
                                "step": engine.step})
                    engine.params_full, engine.opt_state = full["params"], full["opt"]
                    print(f"[recover] restored checkpoint step {step0} (Fig. 8b)")
                engine.apply_plan(adaptation.plan)
                reconfigs.append(it)
                placements.append(placement(it))

            batch = {k: jnp.asarray(v) for k, v in ds.batch_at(it).items()}
            t0 = time.perf_counter()
            loss, _ = engine.run_iteration(batch)
            dt = time.perf_counter() - t0
            losses.append(loss)
            if ckpt:
                ckpt.maybe_save(
                    {"params": engine.params_full, "opt": engine.opt_state,
                     "step": engine.step}, it + 1, extra={"loss": loss})
            if it % max(args.steps // 10, 1) == 0 or it == args.steps - 1:
                print(f"[train] step {it} loss {loss:.4f} {dt*1e3:.0f} ms "
                      f"plan={engine.plan.summary()}")
    return {"losses": losses, "reconfigs": reconfigs, "placements": placements}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized same-family config")
    ap.add_argument("--mode", choices=("spmd", "pipeline"), default="spmd")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--inject-failstop", default=None,
                    help="step:device[,step:device]")
    ap.add_argument("--inject-failslow", default=None,
                    help="step:device@factor[,...]")
    ap.add_argument("--out", default=None)
    ap.add_argument("--profile-dir", default=None,
                    help="capture a profile of the steps after the first two into this "
                         "directory (XProf / TensorBoard); spans in repro.tracing")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
        if args.mode == "pipeline":
            import dataclasses
            need = max(args.pp * len(cfg.period) * 2, 4)
            cfg = reduce_cfg(get_arch(args.arch), n_layers=need)
    print(f"[train] arch={cfg.arch_id} params={cfg.param_count()/1e6:.1f}M "
          f"mode={args.mode}")
    result = run_spmd(cfg, args) if args.mode == "spmd" else run_pipeline(cfg, args)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, default=float))
    print(f"[train] done; final loss {result['losses'][-1]:.4f}")
    return result


if __name__ == "__main__":
    main()
