"""Optimizers: AdamW and Adafactor(+momentum).

Adafactor (factored second moment, bf16 momentum) is the default above 20B
parameters: on v5e (16 GB HBM) fp32 Adam moments for a 398B model exceed the
whole pod's HBM; factored-v + bf16-m is the standard TPU answer (T5X/MaxText).
Optimizer state inherits each parameter's sharding (ZeRO-1 comes free: the
FSDP axis of the param spec shards the moments too).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro import tracing


@dataclass(frozen=True)
class Optimizer:
    name: str
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any, Any], tuple]  # (grads, state, params, step) -> (params, state)
    lr: float


def _tree_map(f, *trees, **kw):
    return jax.tree.map(f, *trees, **kw)


def make_optimizer(name="adamw", lr=3e-4, b1=0.9, b2=0.95, eps=1e-8,
                   weight_decay=0.01, momentum_dtype=jnp.float32):
    if name == "adamw":
        def init(params):
            z = _tree_map(jnp.zeros_like, params)
            return {"m": z, "v": _tree_map(jnp.zeros_like, params)}

        def update(grads, state, params, step):
            with tracing.scope("optimizer"):
                stepf = step.astype(jnp.float32) + 1.0
                bc1 = 1.0 - b1 ** stepf
                bc2 = 1.0 - b2 ** stepf
                m = _tree_map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
                v = _tree_map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g), state["v"], grads)
                def upd(p, m_, v_):
                    u = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
                    return (p - lr * (u + weight_decay * p)).astype(p.dtype)
                params = _tree_map(upd, params, m, v)
                return params, {"m": m, "v": v}

        return Optimizer("adamw", init, update, lr)

    if name == "adafactor":
        def _factored(shape):
            return len(shape) >= 2

        def init(params):
            def vstate(p):
                if _factored(p.shape):
                    return {
                        "vr": jnp.zeros(p.shape[:-1], jnp.float32),
                        "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32),
                    }
                return {"v": jnp.zeros_like(p, dtype=jnp.float32)}

            return {
                "m": _tree_map(lambda p: jnp.zeros_like(p, dtype=momentum_dtype), params),
                "v": _tree_map(vstate, params, is_leaf=lambda x: hasattr(x, "shape")),
            }

        def update(grads, state, params, step):
            with tracing.scope("optimizer"):
                stepf = step.astype(jnp.float32) + 1.0
                decay = 1.0 - stepf ** -0.8  # t^-0.8 schedule (Adafactor paper)

                def upd(p, g, m, v):
                    g = g.astype(jnp.float32)
                    g2 = jnp.square(g) + 1e-30
                    if _factored(p.shape):
                        vr = decay * v["vr"] + (1 - decay) * g2.mean(axis=-1)
                        vc = decay * v["vc"] + (1 - decay) * g2.mean(axis=-2)
                        vhat = vr[..., None] * vc[..., None, :] / jnp.maximum(
                            vr.mean(axis=-1)[..., None, None], 1e-30
                        )
                        new_v = {"vr": vr, "vc": vc}
                    else:
                        vhat = decay * v["v"] + (1 - decay) * g2
                        new_v = {"v": vhat}
                    u = g * jax.lax.rsqrt(vhat + 1e-30)
                    # update clipping (RMS <= 1)
                    rms = jnp.sqrt(jnp.mean(jnp.square(u)) + 1e-30)
                    u = u / jnp.maximum(1.0, rms)
                    new_m = (b1 * m.astype(jnp.float32) + (1 - b1) * u).astype(m.dtype)
                    new_p = (p - lr * (new_m.astype(jnp.float32) + weight_decay * p)).astype(p.dtype)
                    return new_p, new_m, new_v

                flat_p, treedef = jax.tree.flatten(params)
                flat_g = treedef.flatten_up_to(grads)
                flat_m = treedef.flatten_up_to(state["m"])
                flat_v = treedef.flatten_up_to(state["v"])
                out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
                params = treedef.unflatten([o[0] for o in out])
                m = treedef.unflatten([o[1] for o in out])
                v = treedef.unflatten([o[2] for o in out])
                return params, {"m": m, "v": v}

        return Optimizer("adafactor", init, update, lr)

    raise ValueError(name)


def optimizer_for(cfg, lr=3e-4):
    """Pick the optimizer by model scale (HBM-driven)."""
    big = cfg.param_count() > 20_000_000_000
    return make_optimizer(
        "adafactor" if big else "adamw",
        lr=lr,
        momentum_dtype=jnp.bfloat16 if big else jnp.float32,
    )
