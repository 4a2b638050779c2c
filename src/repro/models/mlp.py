"""Dense gated FFN (SwiGLU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import tracing
from repro.models.layers import dense_init
from repro.parallel.sharding import annotate


def init_mlp(key, cfg):
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": annotate(dense_init(ks[0], (D, F)), "dmodel", "ffn"),
        "w_up": annotate(dense_init(ks[1], (D, F)), "dmodel", "ffn"),
        "w_down": annotate(dense_init(ks[2], (F, D)), "ffn", "dmodel"),
    }


def mlp(cfg, p, x, policy):
    with tracing.scope("mlp"):
        h = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
        h = jax.nn.silu(h) * u
        h = policy.constrain(h, "batch", "seq", "ffn")
        return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))
