"""Host-orchestrated pipeline-parallel engine (paper §7's runtime, in JAX).

The Scheduler emits per-stage instruction streams (Forward / Backward /
SendAct / RecvAct / reduce); a lightweight interpreter executes them against
per-stage meshes. This is the engine that *actually runs* ParallelPlans —
reduced configs on the CPU container's host devices, the same code on a TPU
slice — and is what the fault-injection integration tests drive end to end
(kill a device, Scheduler re-plans, recovery reshards, training resumes).

Key properties:
  * per-stage meshes over explicit device sets -> heterogeneous TP degrees
    across stages/replicas are first-class (§6.1);
  * stage boundaries move tensors with `jax.device_put` (resharding-on-
    transfer = the §7 scatter/gather rule in XLA terms);
  * backward recomputes the stage forward under `jax.vjp` (activation
    recomputation — only boundary activations are stored);
  * DP gradient reduction is exact averaging across replica groups;
  * micro-batch migration executes a chunk on a peer replica's stage params
    (replicas are synchronized, so the math is identical — Fig. 6b).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.scheduler.plan import ParallelPlan
from repro.engine.schedules import make_schedule
from repro.launch.mesh import make_stage_mesh
from repro.models.layers import rms_norm
from repro.models.model import apply_layer, embed_tokens, init_params, lm_logits
from repro.parallel.sharding import (
    NULL_POLICY,
    ShardingPolicy,
    policy_for_mesh,
    split_annotations,
)


def _mb_loss(cfg, logits, labels):
    """-> (nll_sum, n_tokens): summed so the host can form the exact global
    token-weighted mean across micro-batches and replicas."""
    mask = (labels >= 0).astype(jnp.float32)
    labels_c = jnp.maximum(labels, 0)
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels_c[..., None], axis=-1)[..., 0]
    nll = (lse - ll) * mask
    return nll.sum(), mask.sum()


class PipelineEngine:
    """Executes one ParallelPlan with real per-stage computation."""

    def __init__(self, cfg, plan: ParallelPlan, *, optimizer=None, seed=0,
                 devices=None, flash_chunk=None):
        self.cfg = cfg
        self.optimizer = optimizer
        self.devices = devices if devices is not None else jax.devices()
        self.flash_chunk = flash_chunk
        # full list-layout params (fp32 master), replicated across replicas
        annotated = init_params(jax.random.PRNGKey(seed), cfg)
        self.params_full, self.axes_full = split_annotations(annotated)
        self.opt_state = optimizer.init(self.params_full) if optimizer else None
        # one compiled update that reuses the old state's buffers: the master
        # state lives on the default device, which otherwise holds old and
        # new params and moments at once
        self._update = (jax.jit(optimizer.update, donate_argnums=(1, 2))
                        if optimizer else None)
        self.step = 0
        self.plan = None
        self.meshes: dict = {}
        self.policies: dict = {}
        self.apply_plan(plan)

    # ----------------------------------------------------------- plan mgmt
    def _mesh_for(self, stage_plan):
        n = len(self.devices)
        if self.devices[0].platform != "cpu":
            missing = [d for d in stage_plan.devices if d >= n]
            if missing:
                raise ValueError(
                    f"plan names devices {missing} but only {n} exist")
            devs = [self.devices[d] for d in stage_plan.devices]
            return make_stage_mesh(devs, 1, len(devs))
        # CPU smoke runs with fewer host devices than the plan names: wrap
        # ids and collapse a stage to one device — semantics kept, TP emulated
        devs = [self.devices[d % n] for d in stage_plan.devices]
        uniq = list(dict.fromkeys(devs))
        if len(uniq) < len(devs):
            devs = uniq[:1]
        return make_stage_mesh(devs, 1, len(devs))

    def apply_plan(self, plan: ParallelPlan):
        """(Re)build meshes + per-stage placements for a plan — the JAX
        analogue of 'destroy and rebuild communication groups'."""
        with tracing.span("apply_plan"):
            self.plan = plan
            self.meshes, self.policies = {}, {}
            self._jit_cache = {}  # stage fns close over plan/policies: invalidate
            for r, rep in enumerate(plan.replicas):
                for s, st in enumerate(rep.stages):
                    if not st.devices:
                        continue
                    mesh = self._mesh_for(st)
                    self.meshes[(r, s)] = mesh
                    pol = policy_for_mesh(mesh, shard_batch=False)
                    tp = pol.tp
                    if tp and self.cfg.n_heads % tp == 0:
                        pol = pol.replace(attn_shard="heads")
                    elif tp and self.cfg.head_dim % tp == 0:
                        pol = pol.replace(attn_shard="head_dim")
                    else:
                        pol = pol.replace(attn_shard=None)
                    self.policies[(r, s)] = pol

    def stage_params(self, r: int, s: int):
        """Stage layer params + (first/last extras), placed on the stage mesh."""
        st = self.plan.replicas[r].stages[s]
        pol = self.policies[(r, s)]
        layers = [self.params_full["layers"][l] for l in st.layers]
        ax_layers = [self.axes_full["layers"][l] for l in st.layers]
        p = {"layers": layers}
        ax = {"layers": ax_layers}
        if s == 0:
            p["embed"] = self.params_full["embed"]
            ax["embed"] = self.axes_full["embed"]
        if s == self.plan.replicas[r].pp - 1:
            p["final_norm"] = self.params_full["final_norm"]
            ax["final_norm"] = self.axes_full["final_norm"]
            if "lm_head" in self.params_full:
                p["lm_head"] = self.params_full["lm_head"]
                ax["lm_head"] = self.axes_full["lm_head"]
        shardings = jax.tree.map(
            lambda a, v: pol.sharding_for(a, v.shape), ax, p,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, str) or e is None for e in x),
        )
        placed = jax.tree.map(
            lambda v, sh: jax.device_put(v, sh) if sh is not None else v, p, shardings)
        return placed, ax

    # ----------------------------------------------------- stage functions
    def _md(self, batch_mb):
        seg = batch_mb["segment_ids"]
        B, S = seg.shape
        return {
            "segment_ids": seg,
            "positions": batch_mb["positions"],
            "abs_positions": jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)),
        }

    def _stage_apply(self, r, s, p, x, md, *, tokens=None, labels=None):
        cfg, pol = self.cfg, self.policies[(r, s)]
        st = self.plan.replicas[r].stages[s]
        md = dict(md)  # static fields stay out of the traced arguments
        md["flash_chunk"] = self.flash_chunk or max(int(md["segment_ids"].shape[1]) // 2, 16)
        md["causal"] = True
        if s == 0:
            x = embed_tokens(cfg, p, tokens)
        for i, l in enumerate(st.layers):
            spec = cfg.layer_spec(l)
            x, _ = apply_layer(cfg, spec, p["layers"][i], x, md, pol)
        if s == self.plan.replicas[r].pp - 1:
            with tracing.scope("lm_head"):
                x = rms_norm(x, p["final_norm"], cfg.norm_eps)
                logits = lm_logits(cfg, p, x, pol)
                return _mb_loss(cfg, logits, labels)
        return x

    def _stage_fn(self, kind, r, s):
        """The jitted forward ("F") or forward+vjp ("B") of stage s on replica
        r's mesh, and whether this call built it (its first call compiles)."""
        key = (kind, r, s)
        built = key not in self._jit_cache
        if built:
            def fwd(p, x, md, tokens, labels):
                return self._stage_apply(r, s, p, x, md, tokens=tokens, labels=labels)

            def bwd(p, x, md, g, tokens, labels):
                _, vjp = jax.vjp(
                    lambda p, x: self._stage_apply(
                        r, s, p, x, md, tokens=tokens, labels=labels),
                    p, x)
                return vjp(g)

            self._jit_cache[key] = jax.jit(fwd if kind == "F" else bwd)
        return self._jit_cache[key], built

    # -------------------------------------------------------- interpreter
    def run_iteration(self, batch, *, placement: Optional[dict] = None):
        """One training iteration: interpret the schedule's instruction
        streams per (replica, stage). Returns (mean_loss, grads_applied).

        placement: optional {ChunkId -> (replica, stage)} micro-batch
        migration overrides from the Scheduler (Fig. 6b).
        """
        with tracing.step(self.step):
            return self._iterate(batch, placement or {})

    def _iterate(self, batch, placement):
        cfg, plan = self.cfg, self.plan
        dp, pp, n_mb = plan.dp, plan.replicas[0].pp, plan.microbatches
        B = batch["tokens"].shape[0]
        assert B % (dp * n_mb) == 0, (B, dp, n_mb)
        mb_size = B // (dp * n_mb)

        def mb_slice(r, m):
            lo = (r * n_mb + m) * mb_size
            return {k: v[lo: lo + mb_size] for k, v in batch.items()}

        params = {}
        with tracing.span("stage_params") as placed:
            for r in range(dp):
                for s in range(pp):
                    params[(r, s)], _ = self.stage_params(r, s)
            placed.set_metadata(bytes=tracing.nbytes(params))

        acts: dict = {}  # (r, m, s) -> boundary activation into stage s
        grads_in: dict = {}  # (r, m, s) -> gradient flowing into stage s's output
        losses = []
        grad_acc: dict = {}

        schedules = {}
        for r in range(dp):
            schedules.update(make_schedule(plan.schedule, pp, n_mb, replica=r))

        # topological interpretation: round-robin over executors, running the
        # head instruction when its inputs are available (host = orchestrator)
        queues = {e: list(order) for e, order in schedules.items()}
        done: set = set()
        progress = True
        while any(queues.values()):
            if not progress:
                raise RuntimeError("pipeline interpreter deadlock")
            progress = False
            for e, q in queues.items():
                if not q:
                    continue
                cid = q[0]
                r, s, m = cid.replica, cid.stage, cid.mb
                exec_rs = placement.get(cid, (r, s))
                mb = mb_slice(r, m)
                md = self._md(mb)
                if cid.kind == "F":
                    if s > 0 and (r, m, s) not in acts:
                        continue
                    p = params[exec_rs]
                    x_in = acts.get((r, m, s))
                    if s == 0:
                        x_in = jnp.zeros((mb_size, 1), jnp.float32)  # unused
                    fwd, built = self._stage_fn("F", exec_rs[0], s)
                    with tracing.span("F", replica=r, stage=s, mb=m, built=int(built)):
                        out = fwd(p, x_in, md, mb["tokens"] if s == 0 else None,
                                  mb["labels"] if s == pp - 1 else None)
                    if s == pp - 1:
                        losses.append(out)  # (nll_sum, n_tokens)
                        grads_in[(r, m, s)] = (
                            jnp.ones((), jnp.float32), jnp.zeros((), jnp.float32))
                    else:
                        nxt = (r, s + 1)
                        tgt_pol = self.policies.get(placement.get(
                            type(cid)("F", m, s + 1, r), nxt))
                        y = out
                        if tgt_pol is not None and tgt_pol.mesh is not None:
                            with tracing.span("send", bytes=tracing.nbytes(y)):
                                y = jax.device_put(
                                    y, tgt_pol.sharding_for(("batch", "seq", None), y.shape))
                        acts[(r, m, s + 1)] = y  # SendAct -> RecvAct
                    done.add(cid)
                    q.pop(0)
                    progress = True
                elif cid.kind == "B":
                    if (r, m, s) not in grads_in:
                        continue
                    p = params[exec_rs]
                    x_in = acts.get((r, m, s))
                    if s == 0:
                        x_in = jnp.zeros((mb_size, 1), jnp.float32)
                    g = grads_in.pop((r, m, s))
                    bwd, built = self._stage_fn("B", exec_rs[0], s)
                    with tracing.span("B", replica=r, stage=s, mb=m, built=int(built)):
                        p_grad, x_grad = bwd(p, x_in, md, g, mb["tokens"] if s == 0 else None,
                                             mb["labels"] if s == pp - 1 else None)
                    key = (r, s)
                    if key not in grad_acc:
                        grad_acc[key] = p_grad
                    else:
                        grad_acc[key] = jax.tree.map(jnp.add, grad_acc[key], p_grad)
                    if s > 0:
                        prev_pol = self.policies[(r, s - 1)]
                        with tracing.span("send", bytes=tracing.nbytes(x_grad)):
                            gx = jax.device_put(
                                x_grad,
                                prev_pol.sharding_for(("batch", "seq", None), x_grad.shape))
                        grads_in[(r, m, s - 1)] = gx
                    acts.pop((r, m, s), None)
                    done.add(cid)
                    q.pop(0)
                    progress = True
                else:  # W chunks: weight grads were folded into B here
                    done.add(cid)
                    q.pop(0)
                    progress = True

        with tracing.span("loss_sync"):
            nll_total = sum(float(l[0]) for l in losses)
            ntok_total = sum(float(l[1]) for l in losses)
        loss = nll_total / max(ntok_total, 1.0)
        self._apply_grads(grad_acc, ntok_total)
        return float(loss), grad_acc

    # ------------------------------------------------------------- update
    def _apply_grads(self, grad_acc, total_tokens):
        """DP-reduce per-stage grads, scatter into the full tree, update."""
        if self.optimizer is None:
            return
        with tracing.span("apply_grads", bytes=tracing.nbytes(grad_acc)):
            cfg, plan = self.cfg, self.plan
            dp, pp = plan.dp, plan.replicas[0].pp
            full_grads = jax.tree.map(jnp.zeros_like, self.params_full)
            for s in range(pp):
                st = plan.replicas[0].stages[s]
                reduced = None
                for r in range(dp):
                    g = grad_acc.get((r, s))
                    if g is None:
                        continue
                    g = jax.device_get(g)
                    reduced = g if reduced is None else jax.tree.map(np.add, reduced, g)
                if reduced is None:
                    continue
                scale = 1.0 / max(total_tokens, 1.0)
                reduced = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32) * scale, reduced)
                for i, l in enumerate(st.layers):
                    full_grads["layers"][l] = reduced["layers"][i]
                if s == 0:
                    full_grads["embed"] = reduced["embed"]
                if s == pp - 1:
                    full_grads["final_norm"] = reduced["final_norm"]
                    if "lm_head" in reduced:
                        full_grads["lm_head"] = reduced["lm_head"]
            self.params_full, self.opt_state = self._update(
                full_grads, self.opt_state, self.params_full, jnp.asarray(self.step))
            self.step += 1
