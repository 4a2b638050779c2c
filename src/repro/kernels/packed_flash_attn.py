"""Segment-aware (packed) flash attention — Pallas TPU kernel.

The compute hot spot behind the paper's Eq. 1 cost model: with sequence
packing, attention cost is proportional to sum(l_i^2), not N^2 — *if* the
kernel skips (q-block, k-block) tiles that the block-diagonal packing mask
rules out. This kernel makes the paper's cost model physically true on TPU:

  * grid (B, H, nQ, nK) with the KV dimension innermost ("arbitrary"
    semantics) so flash accumulators live in VMEM scratch across KV steps;
  * per-tile skip predicate from precomputed block metadata (segment-id and
    position ranges), scalar-prefetched into SMEM: tiles with no segment
    overlap, or entirely above the causal diagonal / outside the sliding
    window, execute no MXU work;
  * BlockSpec tiling: q (1,1,bq,dh), k/v (1,1,bk,dh) in VMEM; bq=bk=128 by
    default — MXU-aligned (128x128) and small enough that q,k,v,acc tiles
    (~4 x 128 x head_dim x 4B) stay well under the ~16 MB v5e VMEM budget;
  * fp32 accumulation with the standard running-max/sum correction;
  * GQA via index-map head folding (kv head = h * K // H).

Validated in interpret mode against `repro.kernels.ref.packed_attention_ref`
across shape/dtype/window sweeps in tests/test_kernels.py; compiled for a
described v5e chip in tests/test_chip_compile.py; run natively against the
reference by chip_smoke.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(
    # scalar prefetch (SMEM): flat (B * nQ * nK,) tile-skip table
    blk_ok_ref,
    # inputs (per BlockSpec tile)
    q_ref, k_ref, v_ref, segq_ref, segk_ref, posq_ref, posk_ref,
    # output
    o_ref,
    # scratch
    acc_ref, m_ref, l_ref,
    *, scale, causal, window, n_q_blocks, n_k_blocks,
):
    b, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(blk_ok_ref[(b * n_q_blocks + iq) * n_k_blocks + ik] != 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)

        seg_q = segq_ref[0]  # (bq, 1) column
        seg_k = segk_ref[0]  # (1, bk) row
        pos_q = posq_ref[0]
        pos_k = posk_ref[0]
        mask = (seg_q == seg_k) & (seg_q != 0)
        if causal:
            mask &= pos_q >= pos_k
        if window is not None:
            mask &= (pos_q - pos_k) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0, acc_ref[...] / safe, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def block_metadata(seg_q, seg_k, pos_q, pos_k, bq, bk, *, causal, window):
    """(B, nQ, nK) int8: 1 iff the tile can contain a visible (q, k) pair.

    Range tests on per-block (min, max) of segment ids and positions: a tile
    is skipped when segment ranges cannot intersect (all-q-max < all-k-min or
    vice versa — exact when ids are sorted, which packing guarantees), when
    it is entirely above the causal diagonal, or entirely left of the window.
    """
    B, Sq = seg_q.shape
    Sk = seg_k.shape[1]
    nq, nk = Sq // bq, Sk // bk
    sq = seg_q.reshape(B, nq, bq)
    sk = seg_k.reshape(B, nk, bk)
    pq = pos_q.reshape(B, nq, bq)
    pk = pos_k.reshape(B, nk, bk)
    # ignore padding (seg==0) in q-range mins via masking with large value
    big = jnp.int32(1 << 30)
    sq_min = jnp.where(sq != 0, sq, big).min(-1)
    sq_max = sq.max(-1)
    sk_min = jnp.where(sk != 0, sk, big).min(-1)
    sk_max = sk.max(-1)
    overlap = (sq_min[:, :, None] <= sk_max[:, None, :]) & (
        sk_min[:, None, :] <= sq_max[:, :, None]
    ) & (sq_max[:, :, None] != 0) & (sk_max[:, None, :] != 0)
    ok = overlap
    if causal:
        ok &= pq.max(-1)[:, :, None] >= pk.min(-1)[:, None, :]
    if window is not None:
        ok &= (pq.max(-1)[:, :, None] - pk.min(-1)[:, None, :]) < window + bq + bk
    return ok.astype(jnp.int8)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"),
)
def packed_flash_attention(q, k, v, seg_q, seg_k, pos_q, pos_k, *,
                           causal=True, window=None, scale=None,
                           block_q=128, block_k=128, interpret=False):
    """q (B,Sq,H,dh); k/v (B,Sk,K,dh) -> (B,Sq,H,dh). See module docstring."""
    B, Sq, H, dh = q.shape
    Sk, K = k.shape[1], k.shape[2]
    if scale is None:
        scale = dh ** -0.5
    bq, bk = min(block_q, Sq), min(block_k, Sk)

    # pad sequence dims to block multiples (padding has seg id 0 => masked)
    def pad_to(x, axis, mult):
        pad = (-x.shape[axis]) % mult
        if pad == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths)

    q_p = pad_to(q, 1, bq)
    k_p = pad_to(k, 1, bk)
    v_p = pad_to(v, 1, bk)
    seg_q_p = pad_to(seg_q, 1, bq)
    seg_k_p = pad_to(seg_k, 1, bk)
    pos_q_p = pad_to(pos_q, 1, bq)
    pos_k_p = pad_to(pos_k, 1, bk)
    Sq_p, Sk_p = q_p.shape[1], k_p.shape[1]
    nq, nk = Sq_p // bq, Sk_p // bk

    blk_ok = block_metadata(seg_q_p, seg_k_p, pos_q_p, pos_k_p, bq, bk,
                            causal=causal, window=window)

    # (B, H, S, dh) layout for clean tiles
    qt = q_p.transpose(0, 2, 1, 3)
    kt = k_p.transpose(0, 2, 1, 3)
    vt = v_p.transpose(0, 2, 1, 3)
    # TPU tiling wants the last two block dims to be multiples of (8, 128) or
    # whole: q-side ids become (B, S, 1) columns, k-side ids (B, 1, S) rows,
    # so the (bq, bk) mask is a broadcast with no in-kernel transpose.
    col = lambda x: x[:, :, None]
    row = lambda x: x[:, None, :]

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        n_q_blocks=nq, n_k_blocks=nk)

    kv_head = lambda h: h * K // H
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # blk_ok, flattened into SMEM
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, iq, ik, ok: (b, h, iq, 0)),  # q
            pl.BlockSpec((1, 1, bk, dh), lambda b, h, iq, ik, ok: (b, kv_head(h), ik, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b, h, iq, ik, ok: (b, kv_head(h), ik, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, h, iq, ik, ok: (b, iq, 0)),  # seg_q
            pl.BlockSpec((1, 1, bk), lambda b, h, iq, ik, ok: (b, 0, ik)),  # seg_k
            pl.BlockSpec((1, bq, 1), lambda b, h, iq, ik, ok: (b, iq, 0)),  # pos_q
            pl.BlockSpec((1, 1, bk), lambda b, h, iq, ik, ok: (b, 0, ik)),  # pos_k
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b, h, iq, ik, ok: (b, h, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),  # acc
            pltpu.VMEM((bq, 1), jnp.float32),  # running max
            pltpu.VMEM((bq, 1), jnp.float32),  # running sum
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(blk_ok.astype(jnp.int32).reshape(-1), qt, kt, vt,
      col(seg_q_p), row(seg_k_p), col(pos_q_p), row(pos_k_p))
    out = out.transpose(0, 2, 1, 3)
    return out[:, :Sq]


def skipped_block_fraction(seg, pos, bq, bk, *, causal=True, window=None):
    """Fraction of (q,k) tiles skipped for a packed batch — the measured
    counterpart of the paper's sum(l^2)/N^2 ratio."""
    meta = block_metadata(seg, seg, pos, pos, bq, bk, causal=causal, window=window)
    return 1.0 - float(meta.mean())
