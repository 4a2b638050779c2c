"""pjit train/serve/prefill step builders.

train_step: microbatched gradient accumulation via lax.scan (comm/compute
overlap falls out of the scan structure under XLA's latency-hiding scheduler),
global-norm clipping, optimizer update. Mixed precision: fp32 master params,
bf16 compute, configurable accumulation dtype.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import tracing
from repro.models.model import (
    forward_train,
    loss_fn,
    prefill_forward,
    serve_forward,
    stacked_init,
)
from repro.parallel.sharding import ShardingPolicy, split_annotations


@dataclass
class TrainState:
    params: Any
    opt: Any
    step: Any

    def tree(self):
        return {"params": self.params, "opt": self.opt, "step": self.step}


def init_train_state(key, cfg, optimizer):
    annotated = stacked_init(key, cfg)
    params, axes = split_annotations(annotated)
    opt = optimizer.init(params)
    return {"params": params, "opt": opt, "step": jnp.zeros((), jnp.int32)}, axes


def state_axes(cfg, optimizer):
    """Logical-axes tree for the full train state (for sharding without init)."""
    key = jax.random.PRNGKey(0)
    annotated = jax.eval_shape(lambda k: stacked_init(k, cfg), key)
    # eval_shape maps Annot -> Annot with ShapeDtypeStruct values
    params_s, axes = split_annotations(annotated)
    opt_s = jax.eval_shape(optimizer.init, params_s)
    return params_s, opt_s, axes


def sharding_for_state(policy: ShardingPolicy, cfg, optimizer):
    """NamedSharding trees for (params, opt, step) + the state ShapeDtypeStructs."""
    params_s, opt_s, axes = state_axes(cfg, optimizer)

    def pspec(ax, sds):
        return policy.sharding_for(ax, sds.shape)

    params_sh = jax.tree.map(
        lambda ax, s: pspec(ax, s), axes, params_s,
        is_leaf=lambda x: isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x),
    )

    # Optimizer state mirrors param sharding; factored Adafactor leaves drop
    # the corresponding logical axis (vr drops the last dim, vc the -2nd).
    def map_state(sub):
        def per(ax, s_param, st):
            if isinstance(st, dict):  # adafactor v
                out = {}
                for k, leaf in st.items():
                    if k == "vr":
                        out[k] = pspec(ax[:-1], leaf)
                    elif k == "vc":
                        out[k] = pspec(ax[:-2] + ax[-1:], leaf)
                    else:
                        out[k] = pspec(ax, leaf)
                return out
            return pspec(ax, st)

        return jax.tree.map(
            per, axes, params_s, sub,
            is_leaf=lambda x: isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x),
        )

    opt_sh = {k: map_state(v) for k, v in opt_s.items()}
    step_sh = policy.sharding_for((), ()) if policy.mesh else None
    state_sh = {"params": params_sh, "opt": opt_sh, "step": step_sh}
    state_s = {"params": params_s, "opt": opt_s, "step": jax.ShapeDtypeStruct((), jnp.int32)}
    return state_sh, state_s, axes


def global_norm(tree):
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree))
    )


def build_train_step(cfg, policy: ShardingPolicy, optimizer, *, microbatches=1,
                     remat=True, flash_chunk=1024, use_scan=True, clip_norm=1.0,
                     accum_dtype=jnp.float32):
    """Returns train_step(state, batch) -> (state, metrics)."""

    def mb_loss(params, mb):
        return loss_fn(cfg, params, mb, policy, use_scan=use_scan, remat=remat,
                       flash_chunk=flash_chunk)

    grad_fn = jax.value_and_grad(mb_loss, has_aux=True)

    def train_step(state, batch):
        params = state["params"]

        def split_mb(x):
            x = x.reshape((microbatches, x.shape[0] // microbatches) + x.shape[1:])
            return x

        mbs = jax.tree.map(split_mb, batch)

        def accum(carry, mb):
            gacc, lacc = carry
            (loss, metrics), grads = grad_fn(params, mb)
            grads = jax.tree.map(lambda a, g: a + g.astype(accum_dtype), gacc, grads)
            return (grads, lacc + loss), metrics

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)
        (grads, loss_sum), metrics = jax.lax.scan(accum, (zeros, jnp.zeros((), jnp.float32)), mbs)
        with tracing.scope("optimizer"):
            grads = jax.tree.map(lambda g: (g / microbatches).astype(jnp.float32), grads)
            gnorm = global_norm(grads)
            scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
        new_params, new_opt = optimizer.update(grads, state["opt"], params, state["step"])
        new_state = {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
        out_metrics = {
            "loss": loss_sum / microbatches,
            "grad_norm": gnorm,
            "ntokens": metrics["ntokens"].sum(),
        }
        return new_state, out_metrics

    return train_step


def build_serve_step(cfg, policy: ShardingPolicy, *, sample="greedy"):
    """serve_step(params, cache, batch) -> (next_tokens, logits, cache)."""

    def serve_step(params, cache, batch):
        logits, cache = serve_forward(cfg, params, cache, batch, policy)
        next_tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tokens, logits, cache

    return serve_step


def build_prefill_step(cfg, policy: ShardingPolicy, *, flash_chunk=1024, use_scan=True):
    """prefill_step(params, batch) -> (last_logits, caches)."""

    def prefill_step(params, batch):
        return prefill_forward(cfg, params, batch, policy, use_scan=use_scan,
                               flash_chunk=flash_chunk)

    return prefill_step
