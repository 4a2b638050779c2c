"""GQA attention: packed-segment masks, SWA, qk-norm, M-RoPE, KV-cache decode.

The core of a call with no KV cache (training, prefill) is the Pallas packed
flash attention (`repro.kernels.packed_flash_attn`) when the program is
lowered for a TPU and no mesh axis splits its inputs: it skips the tiles the
packing mask rules out, forward and backward. Everywhere else (the CPU, a
head-sharded stage, decode) the jnp paths with the same semantics compute
it: dense masked softmax up to 2 * flash_chunk keys, else a lax.scan over KV
chunks with running max/sum.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import tracing
from repro.kernels.packed_flash_attn import BLOCK_K, BLOCK_Q, packed_flash_attention
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


def _splits(policy, axes, shape):
    """Whether the policy lays an array of these logical axes over more than
    one device."""
    return any(policy.axis_size(a) > 1 for a in policy.spec_for(axes, shape))


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

        def jnp_core(q, k_all, v_all, seg_q, seg_k, pos_q, pos_k):
            # expand KV heads to H query heads (GQA)
            if k_all.shape[2] != H:
                rep = H // k_all.shape[2]
                k_all = jnp.repeat(k_all, rep, axis=2)
                v_all = jnp.repeat(v_all, rep, axis=2)
            with tracing.scope("attn_core"):
                if cache is not None:
                    # decode path: queries are length-1 (or small); dense masked attention
                    mask = _mask(seg_q, seg_k, pos_q, pos_k, causal=causal, window=window)
                    return _sdpa_dense(q, k_all, v_all, mask, scale)
                chunk = md.get("flash_chunk", 1024)
                if k_all.shape[1] <= 2 * chunk:
                    mask = _mask(seg_q, seg_k, pos_q, pos_k, causal=causal, window=window)
                    return _sdpa_dense(q, k_all, v_all, mask, scale)
                return _sdpa_flash_chunked(
                    q, k_all, v_all, seg_q, seg_k, pos_q, pos_k,
                    causal=causal, window=window, scale=scale, chunk=chunk,
                )

        def kernel_core(q, k_all, v_all, seg_q, seg_k, pos_q, pos_k, interpret=False):
            # skips the tiles the packing mask rules out, takes K/V heads
            # through its index maps, and trains through its own backward
            with tracing.scope("attn_core"):
                return packed_flash_attention(
                    q, k_all, v_all, seg_q, seg_k, pos_q, pos_k,
                    causal=causal, window=window, scale=scale,
                    block_q=md.get("kernel_block_q", BLOCK_Q),
                    block_k=md.get("kernel_block_k", BLOCK_K), interpret=interpret)

        if cache is not None:
            pos_q = md["lengths"][:, None] + jnp.arange(S)[None]
            out = jnp_core(q, k_all, v_all, jnp.ones((B, S), jnp.int32), seg_k, pos_q, pos_k)
        else:
            args = (q, k_all, v_all, md["segment_ids"], seg_k, md["abs_positions"], pos_k)
            if md.get("use_pallas_kernel"):  # forced, as CPU tests do: interpreted there
                out = kernel_core(*args, interpret=jax.default_backend() == "cpu")
            elif _splits(policy, ("batch", "seq", "heads", "head_dim"), q.shape) or _splits(
                    policy, ("batch", "seq", "kv_heads", "head_dim"), k_all.shape):
                out = jnp_core(*args)  # GSPMD does not partition a pallas_call
            else:
                # the kernel on TPU; chosen when the program is lowered, so a
                # compile for a described chip from a CPU host takes it too
                out = jax.lax.platform_dependent(*args, tpu=kernel_core, default=jnp_core)

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
