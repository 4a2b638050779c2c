"""Pure-jnp oracle for the packed flash attention kernel.

Dense masked softmax with exactly the kernel's semantics:
  * block-diagonal packing mask (same nonzero segment id),
  * causal mask on *positions* (packed per-document positions),
  * optional sliding window (pos_q - pos_k < window),
  * GQA (kv heads repeated to query heads),
  * rows with no visible key return 0 (matches the kernel's safe divide).

Matmuls run at highest precision, so on a TPU the oracle is truly float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


@jax.default_matmul_precision("highest")
def packed_attention_ref(q, k, v, seg_q, seg_k, pos_q, pos_k, *,
                         causal=True, window=None, scale=None):
    """q (B,Sq,H,dh); k/v (B,Sk,K,dh); seg/pos (B,S) int32 -> (B,Sq,H,dh)."""
    B, Sq, H, dh = q.shape
    K = k.shape[2]
    assert H % K == 0
    if scale is None:
        scale = dh ** -0.5
    if K != H:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    mask = (seg_q[:, :, None] == seg_k[:, None, :]) & (seg_q[:, :, None] != 0)
    if causal:
        mask &= pos_q[:, :, None] >= pos_k[:, None, :]
    if window is not None:
        mask &= (pos_q[:, :, None] - pos_k[:, None, :]) < window
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    s = jnp.where(mask[:, None], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(mask[:, None], p, 0.0)
    l = p.sum(axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    l_q = jnp.swapaxes(l[..., 0], 1, 2)[..., None]  # (B,Sq,H,1)
    o = jnp.where(l_q > 0, o / jnp.maximum(l_q, 1e-30), 0.0)
    return o.astype(q.dtype)
