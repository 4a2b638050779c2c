"""GQA attention: packed-segment masks, SWA, qk-norm, M-RoPE, flash-chunked
training path, KV-cache decode path.

The jnp flash-chunked path (lax.scan over KV chunks with running max/sum) is
the lowering reference; `repro.kernels.packed_flash_attn` is the Pallas TPU
kernel with the same semantics (and block skipping on the segment mask).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import tracing
from repro.models.layers import apply_rope, dense_init, head_rms_norm, rope_angles
from repro.parallel.sharding import annotate

NEG_INF = -1e30


def init_attention(key, cfg):
    D, H, K, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": annotate(dense_init(ks[0], (D, H, dh)), "dmodel", "heads", "head_dim"),
        "wk": annotate(dense_init(ks[1], (D, K, dh)), "dmodel", "kv_heads", "head_dim"),
        "wv": annotate(dense_init(ks[2], (D, K, dh)), "dmodel", "kv_heads", "head_dim"),
        "wo": annotate(dense_init(ks[3], (H, dh, D), in_axis=(0, 1)), "heads", "head_dim", "dmodel"),
    }
    if cfg.qk_norm:
        p["q_norm"] = annotate(jnp.zeros((dh,), jnp.float32), None)
        p["k_norm"] = annotate(jnp.zeros((dh,), jnp.float32), None)
    return p


def _mask(seg_q, seg_k, pos_q, pos_k, *, causal, window):
    """(B, Sq, Sk) bool mask from segment ids + absolute positions."""
    same = (seg_q[:, :, None] == seg_k[:, None, :]) & (seg_q[:, :, None] != 0)
    if causal:
        same &= pos_q[:, :, None] >= pos_k[:, None, :]
    if window is not None:
        same &= (pos_q[:, :, None] - pos_k[:, None, :]) < window
    return same


def _sdpa_dense(q, k, v, mask, scale):
    # q (B,Sq,H,dh) k/v (B,Sk,H,dh) mask (B,Sq,Sk)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def _sdpa_flash_chunked(q, k, v, seg_q, seg_k, pos_q, pos_k, *, causal, window, scale, chunk):
    """lax.scan over KV chunks with running (m, l, acc) — flash semantics."""
    B, Sq, H, dh = q.shape
    Sk = k.shape[1]
    n_chunks = Sk // chunk
    assert Sk % chunk == 0, (Sk, chunk)

    k_c = k.reshape(B, n_chunks, chunk, H, dh).transpose(1, 0, 2, 3, 4)
    v_c = v.reshape(B, n_chunks, chunk, H, dh).transpose(1, 0, 2, 3, 4)
    segk_c = seg_k.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
    posk_c = pos_k.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        m, l, acc = carry
        kc, vc, sc, pc = xs
        mask = _mask(seg_q, sc, pos_q, pc, causal=causal, window=window)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kc).astype(jnp.float32) * scale
        s = jnp.where(mask[:, None], s, NEG_INF)
        # the output does not depend on the running max, so it takes no
        # gradient: differentiating the max divides by a count of ties,
        # which is 0/0 on TPU when XLA recomputes s with bf16 rounding
        # that differs from the forward's
        m_new = jax.lax.stop_gradient(jnp.maximum(m, s.max(axis=-1)))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vc.dtype), vc
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    acc0 = jnp.zeros((B, H, Sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (k_c, v_c, segk_c, posk_c))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,Sq,H,dh)


def attention(cfg, spec, p, x, md, policy, cache=None):
    """Full attention layer.

    md: dict with 'positions' (B,S) or (B,S,3) for M-RoPE, 'segment_ids' (B,S),
        and for decode: 'lengths' (B,) current KV fill.
    cache: None for train/prefill, else {'k': (B,T,K,dh), 'v': ...}.
    Returns (out (B,S,D), new_cache).
    """
    with tracing.scope("attn_proj"):
        D, H, K, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        B, S = x.shape[:2]
        scale = 1.0 / math.sqrt(dh)
        window = cfg.window if spec.attn_kind == "swa" else None
        causal = md.get("causal", True)

        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
        kx = md.get("cross_x")  # encoder output for cross attention
        src = kx if kx is not None else x
        if cache is not None and "k_const" in cache:
            k_all, v_all = cache["k_const"], cache["v_const"]  # precomputed cross KV
            new_cache = cache
            seg_k = md["cross_segment_ids"]
            pos_k = md["cross_positions"]
            causal, window = False, None
        else:
            k = jnp.einsum("bsd,dkh->bskh", src, p["wk"].astype(x.dtype))
            v = jnp.einsum("bsd,dkh->bskh", src, p["wv"].astype(x.dtype))
            if cfg.qk_norm:
                q = head_rms_norm(q, p["q_norm"])
                k = head_rms_norm(k, p["k_norm"])
            if md.get("rope", True) and kx is None:
                ang = rope_angles(md["positions"], dh, cfg.rope_theta, cfg.mrope_sections)
                q = apply_rope(q, ang)
                k = apply_rope(k, ang)
            elif cfg.qk_norm is False and kx is not None:
                pass
            if cache is None:
                k_all, v_all, new_cache = k, v, None
                if kx is not None:  # cross attention over encoder output
                    seg_k = md["cross_segment_ids"]
                    pos_k = md["cross_positions"]
                    causal, window = False, None
                    if md.get("collect_state"):
                        new_cache = {"k_const": k, "v_const": v}
                else:
                    seg_k, pos_k = md["segment_ids"], md["abs_positions"]
                    if md.get("collect_state"):  # prefill: emit the filled KV cache
                        new_cache = {"k": k, "v": v, "pos": pos_k.astype(jnp.int32)}
            else:
                # decode: ring-buffer insert at (position % T). For full-attention
                # layers T == max_len so slot == position; for SWA layers T is
                # 2*window and old slots are overwritten once out of the window.
                idx = md["lengths"]  # (B,)
                rows = jnp.arange(B)
                T = cache["k"].shape[1]
                slot = idx % T
                k_all = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype))
                v_all = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype))
                pos_arr = cache["pos"].at[rows, slot].set(idx.astype(jnp.int32))
                new_cache = {"k": k_all, "v": v_all, "pos": pos_arr}
                pos_k = jnp.maximum(pos_arr, 0)
                seg_k = (pos_arr >= 0).astype(jnp.int32)  # valid cache entries

        # expand KV heads to H query heads (GQA)
        if k_all.shape[2] != H:
            rep = H // k_all.shape[2]
            k_all = jnp.repeat(k_all, rep, axis=2)
            v_all = jnp.repeat(v_all, rep, axis=2)

        with tracing.scope("attn_core"):  # whichever path computes it
            if cache is not None:
                # decode path: queries are length-1 (or small); dense masked attention
                pos_q = md["lengths"][:, None] + jnp.arange(S)[None]
                seg_q = jnp.ones((B, S), jnp.int32)
                mask = _mask(seg_q, seg_k, pos_q, pos_k, causal=causal, window=window)
                out = _sdpa_dense(q, k_all, v_all, mask, scale)
            else:
                pos_q = md["abs_positions"] if kx is None else md["abs_positions"]
                seg_q = md["segment_ids"]
                Sk = k_all.shape[1]
                chunk = md.get("flash_chunk", 1024)
                if md.get("use_pallas_kernel"):
                    # Pallas packed flash attention (block-skipping on the packing
                    # mask): native on TPU, interpret mode elsewhere.
                    from repro.kernels.ops import packed_attention

                    out = packed_attention(
                        q, k_all, v_all, seg_q, seg_k, pos_q, pos_k,
                        causal=causal, window=window, scale=scale,
                        block_q=md.get("kernel_block_q", 128),
                        block_k=md.get("kernel_block_k", 128),
                    )
                elif Sk <= 2 * chunk:
                    mask = _mask(seg_q, seg_k, pos_q, pos_k, causal=causal, window=window)
                    out = _sdpa_dense(q, k_all, v_all, mask, scale)
                else:
                    out = _sdpa_flash_chunked(
                        q, k_all, v_all, seg_q, seg_k, pos_q, pos_k,
                        causal=causal, window=window, scale=scale, chunk=chunk,
                    )

        out = policy.constrain(out, "batch", "seq", "heads", "head_dim")
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
        return y, new_cache


def init_cross_attention(key, cfg):
    return init_attention(key, cfg)


def precompute_cross_kv(cfg, p, enc_out):
    """Cross-attention K/V from encoder output (computed once per request)."""
    k = jnp.einsum("bsd,dkh->bskh", enc_out, p["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dkh->bskh", enc_out, p["wv"].astype(enc_out.dtype))
    return {"k_const": k, "v_const": v}
