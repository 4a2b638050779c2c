"""The SPMD train step on one chip: `repro.launch.train.spmd_step`, the jitted
step `run_spmd` drives, on the benchmark's weights and batches.

Set-up builds the one compiled step and its state, and drives it through the
check's first steps on rows that all differ; the window continues from that
same state with the same call. Each step's batch goes to the device with
`jnp.asarray` and each step blocks on the new state, as `run_spmd` does.
"""
from __future__ import annotations

import math
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from chipbench.runners import common
from chipbench.runners.common import span

class Run:
    def __init__(self, cfg, mix, seed, devices):
        self.cfg, self.seed, self.devices = cfg, seed, devices
        self.traffic = common.traffic_for(cfg, mix, seed)
        self.n_check = cfg["check"]["steps"]

    def setup(self, seconds):
        from repro.launch.train import spmd_step
        from repro.parallel.sharding import NULL_POLICY

        cfg, tr = self.cfg, self.cfg["train"]
        arch = common.arch_module(cfg).arch(cfg)
        opt = common.optimizer(cfg, arch)
        self.step_fn = spmd_step(arch, NULL_POLICY, opt, SimpleNamespace(
            microbatches=tr["microbatches"], seq_len=tr["seq_len"]))
        params = common.program_weights(cfg, self.seed)
        state = {"params": params, "opt": jax.jit(opt.init)(params),
                 "step": jnp.zeros((), jnp.int32)}
        del params  # the state holds it; the first step replaces it
        losses, times = [], []
        for i in range(self.n_check):
            t0 = time.perf_counter()
            state, metrics = self.step_fn(state, self._device_batch(self.traffic.batch(i)))
            jax.block_until_ready(state)
            times.append(time.perf_counter() - t0)
            losses.append(float(metrics["loss"]))
            if i == 0:
                grad_norms = common.first_grad_norms(
                    cfg, state["opt"]["m"], tr["optimizer"]["b1"])
        self.prog = {"losses": losses, "grad_norms": grad_norms,
                     "final": common.host_canonical(cfg, state["params"])}
        self.state = state
        # batches for the window, made now: enough for steps as fast as the
        # fastest check step after the first, twice over; the pool wraps
        # round if the window outruns it
        fastest = min(times[1:] or times)
        self.pool_size = max(8, math.ceil(2 * seconds / max(fastest, 1e-3)))
        self.pool = [self.traffic.batch(self.n_check + i) for i in range(self.pool_size)]
        self.work = [common.step_work(cfg, b) for b in self.pool]

    @staticmethod
    def _device_batch(b):
        return {k: jnp.asarray(v) for k, v in b.items()}

    def window(self, seconds):
        """Steps until `seconds` have passed; -> the step records."""
        steps, state = [], self.state
        self.state = None
        with span("window"):
            start = time.perf_counter()
            while True:
                i = len(steps)
                t0 = time.perf_counter()
                with span("batch_to_device"):
                    batch = self._device_batch(self.pool[i % self.pool_size])
                with span("train_step"):
                    state, metrics = self.step_fn(state, batch)
                with span("block"):
                    jax.block_until_ready(state)
                t1 = time.perf_counter()
                steps.append({"t0": t0, "t1": t1, "loss": metrics["loss"],
                              **self.work[i % self.pool_size]})
                if t1 - start >= seconds:
                    break
        self.state = state
        for s, loss in zip(steps, jax.device_get([s["loss"] for s in steps])):
            s["loss"] = float(loss)
        return steps

    def program_texts(self):
        """The compiled step's text, whose op_name metadata names the
        scopes of the trace's operations."""
        batch = self._device_batch(self.pool[0])
        return [common.fresh_text(self.step_fn, self.state, batch)]

    def free(self):
        self.state = self.step_fn = None

    def check(self):
        batches = [self.traffic.batch(i) for i in range(self.n_check)]
        return common.check(self.cfg, self.seed, self.prog, batches)
