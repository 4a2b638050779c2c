"""Segment-aware (packed) flash attention — Pallas TPU kernels, trainable.

The compute hot spot behind the paper's Eq. 1 cost model: with sequence
packing, attention cost is proportional to sum(l_i^2), not N^2 — *if* the
kernel skips (q-block, k-block) tiles that the block-diagonal packing mask
rules out. These kernels make the paper's cost model physically true on TPU,
forward and backward:

  * one tile table per call, `block_metadata` over (B, nQ, nK): tiles with no
    segment overlap, or entirely above the causal diagonal / outside the
    sliding window, are ruled out. It is scalar-prefetched into SMEM, and a
    ruled-out tile does no MXU work in any of the three kernels. A second
    prefetched table points a ruled-out step's inputs at the next block that
    runs, so that step fetches nothing of its own;
  * forward: grid (B, H, nQ, nK), KV innermost ("arbitrary") so the flash
    accumulators live in VMEM scratch across KV steps; fp32 running max and
    sum; it also emits the per-row logsumexp for the backward pass;
  * backward, from the logsumexp and delta = rowsum(dO * O): a dq kernel over
    (B, H, nQ, nK), KV innermost, and a dk/dv kernel over (B, K, nK, G * nQ)
    with the G = H / K query heads of a KV head and their q blocks innermost,
    so each KV head's gradient is summed in VMEM; each recomputes
    p = exp(s - lse) per tile;
  * the MXU takes its operands in the input dtype (bf16 in training) and
    accumulates in fp32;
  * GQA through the index maps (kv head = h // G): K/V are never repeated;
  * tiles (bq, dh) and (bk, dh); BLOCK_Q and BLOCK_K by default.

`packed_flash_attention` is differentiable in q, k and v (a `custom_vjp`);
segment ids and positions take no gradient. Validated in interpret mode
against `repro.kernels.ref.packed_attention_ref`, values and gradients, in
tests/test_kernels.py; compiled for a described v5e chip in
tests/test_chip_compile.py; run natively against the reference by
chip_smoke.py.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# default tile edges: of the sizes tried at qwen3-8b widths on a v5e, 512 x 512
# ran forward and backward fastest (PERF.md, Findings)
BLOCK_Q = 512
BLOCK_K = 512

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_F32 = jnp.float32


def block_metadata(seg_q, seg_k, pos_q, pos_k, bq, bk, *, causal, window, xp=jnp):
    """(B, nQ, nK) int8: 1 iff the tile can contain a visible (q, k) pair.

    Range tests on per-block (min, max) of segment ids and positions: a tile
    is skipped when segment ranges cannot intersect (all-q-max < all-k-min or
    vice versa — exact when ids are sorted, which packing guarantees), when
    it is entirely above the causal diagonal, or entirely left of the window.
    `xp` is the array module: jnp inside a program, numpy on the host.
    """
    B, Sq = seg_q.shape
    Sk = seg_k.shape[1]
    nq, nk = Sq // bq, Sk // bk
    sq = seg_q.reshape(B, nq, bq)
    sk = seg_k.reshape(B, nk, bk)
    pq = pos_q.reshape(B, nq, bq)
    pk = pos_k.reshape(B, nk, bk)
    # ignore padding (seg==0) in q-range mins via masking with large value
    big = 1 << 30
    sq_min = xp.where(sq != 0, sq, big).min(-1)
    sq_max = sq.max(-1)
    sk_min = xp.where(sk != 0, sk, big).min(-1)
    sk_max = sk.max(-1)
    overlap = (sq_min[:, :, None] <= sk_max[:, None, :]) & (
        sk_min[:, None, :] <= sq_max[:, :, None]
    ) & (sq_max[:, :, None] != 0) & (sk_max[:, None, :] != 0)
    ok = overlap
    if causal:
        ok &= pq.max(-1)[:, :, None] >= pk.min(-1)[:, None, :]
    if window is not None:  # the closest pair, whatever the positions' order
        ok &= (pq.min(-1)[:, :, None] - pk.max(-1)[:, None, :]) < window
    return ok.astype(xp.int8)


def _visible(seg_q, seg_k, pos_q, pos_k, *, causal, window):
    """The exact mask of a tile: q ids as a column and k ids as a row give the
    (bq, bk) mask; q ids as a row and k ids as a column give its transpose."""
    mask = (seg_q == seg_k) & (seg_q != 0)
    if causal:
        mask &= pos_q >= pos_k
    if window is not None:
        mask &= (pos_q - pos_k) < window
    return mask


class _Static(NamedTuple):
    causal: bool
    window: Optional[int]
    scale: float
    bq: int
    bk: int
    interpret: bool


# ------------------------------------------------------------------ kernels
def _fwd_kernel(ok_ref, _at_ref, q_ref, k_ref, v_ref, segq_ref, segk_ref, posq_ref, posk_ref,
                o_ref, lse_ref, acc_ref, m_ref, l_ref, *, st, nq, nk):
    b, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ok_ref[(b * nq + iq) * nk + ik] != 0)
    def _compute():
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0], _NT,
                                preferred_element_type=_F32) * st.scale  # (bq, bk)
        mask = _visible(segq_ref[0], segk_ref[0], posq_ref[0], posk_ref[0],
                        causal=st.causal, window=st.window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=_F32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        seen = l > 0  # rows with no visible key give 0 and a logsumexp of 0
        safe = jnp.where(seen, l, 1.0)
        o_ref[0, 0] = jnp.where(seen, acc_ref[...] / safe, 0.0).astype(o_ref.dtype)
        lse = jnp.where(seen, m_ref[...] + jnp.log(safe), 0.0)  # (bq, 1)
        lse_ref[0, 0] = lse.reshape(1, st.bq)


def _dq_kernel(ok_ref, _at_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               segq_ref, segk_ref, posq_ref, posk_ref, dq_ref, acc_ref, *, st, nq, nk):
    b, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ok_ref[(b * nq + iq) * nk + ik] != 0)
    def _compute():
        k = k_ref[0, 0]
        s = jax.lax.dot_general(q_ref[0, 0], k, _NT, preferred_element_type=_F32) * st.scale
        mask = _visible(segq_ref[0], segk_ref[0], posq_ref[0], posk_ref[0],
                        causal=st.causal, window=st.window)
        lse = lse_ref[0, 0].reshape(st.bq, 1)
        p = jnp.exp(jnp.where(mask, s, NEG_INF) - lse)  # (bq, bk)
        dp = jax.lax.dot_general(do_ref[0, 0], v_ref[0, 0], _NT, preferred_element_type=_F32)
        ds = p * (dp - delta_ref[0, 0].reshape(st.bq, 1))
        acc_ref[...] += jax.lax.dot_general(ds.astype(k.dtype), k, _NN,
                                            preferred_element_type=_F32)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = (acc_ref[...] * st.scale).astype(dq_ref.dtype)


def _dkv_kernel(ok_ref, _at_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                segq_ref, segk_ref, posq_ref, posk_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                *, st, nq, nk, n_inner):
    # tiles are computed transposed, (bk, bq): the q-side rows (logsumexp,
    # delta, ids) then broadcast along lanes as they are stored
    b, ik, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    iq = j % nq  # j runs over (query head of this KV head, q block)

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(ok_ref[(b * nq + iq) * nk + ik] != 0)
    def _compute():
        q, do = q_ref[0, 0], do_ref[0, 0]
        s_t = jax.lax.dot_general(k_ref[0, 0], q, _NT, preferred_element_type=_F32) * st.scale
        mask_t = _visible(segq_ref[0], segk_ref[0], posq_ref[0], posk_ref[0],
                          causal=st.causal, window=st.window)
        p_t = jnp.exp(jnp.where(mask_t, s_t, NEG_INF) - lse_ref[0, 0])  # (bk, bq)
        dv_acc[...] += jax.lax.dot_general(p_t.astype(do.dtype), do, _NN,
                                           preferred_element_type=_F32)
        dp_t = jax.lax.dot_general(v_ref[0, 0], do, _NT, preferred_element_type=_F32)
        ds_t = p_t * (dp_t - delta_ref[0, 0])
        dk_acc[...] += jax.lax.dot_general(ds_t.astype(q.dtype), q, _NN,
                                           preferred_element_type=_F32)

    @pl.when(j == n_inner - 1)
    def _finalize():
        dk_ref[0, 0] = (dk_acc[...] * st.scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


# ------------------------------------------------------------------ calls
# Layout inside the calls: q, o (B, H, Sq, dh); k, v (B, K, Sk, dh); segment
# ids and positions (B, S) int32, padded to block multiples; logsumexp and
# delta (B, H, 1, Sq) f32. TPU tiling wants the last two block dims to be
# multiples of (8, 128) or whole, so ids go in as (B, S, 1) columns or
# (B, 1, S) rows, whichever orientation the kernel's tile broadcasts.
def _col(x):
    return x[:, :, None]


def _row(x):
    return x[:, None, :]


def _table(ids, st):
    seg_q, seg_k, pos_q, pos_k = ids
    return block_metadata(seg_q, seg_k, pos_q, pos_k, st.bq, st.bk,
                          causal=st.causal, window=st.window).astype(jnp.int32)


def _fetch(ok):
    """For each step along the inner grid axis, the block its inputs hold:
    its own where its tile runs, else the next such tile's, else the last
    one's (0 in a row where none runs). A ruled-out step then fetches nothing
    of its own, and the next tile's fetch starts early."""
    n = ok.shape[-1]
    step = jnp.arange(n, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(ok > 0, step, n), axis=ok.ndim - 1, reverse=True)
    last = jax.lax.cummax(jnp.where(ok > 0, step, -1), axis=ok.ndim - 1)
    return jnp.where(nxt < n, nxt, jnp.maximum(last, 0)).reshape(-1)


# batch, head and outer block are independent; the inner axis accumulates
_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _q_major_specs(nq, nk, G, dh, st):
    """Blocks over the grid (B, H, nQ, nK) of the forward and dq kernels: q,
    its logsumexp row and its ids stay for a row of KV steps; K/V and their
    ids follow the fetch table. -> (q, kv, q row, q ids column, k ids row)."""
    at = lambda b, iq, ik, kv_at: kv_at[(b * nq + iq) * nk + ik]
    return (
        pl.BlockSpec((1, 1, st.bq, dh), lambda b, h, iq, ik, *_: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, st.bk, dh),
                     lambda b, h, iq, ik, _, kv_at: (b, h // G, at(b, iq, ik, kv_at), 0)),
        pl.BlockSpec((1, 1, 1, st.bq), lambda b, h, iq, ik, *_: (b, h, 0, iq)),
        pl.BlockSpec((1, st.bq, 1), lambda b, h, iq, ik, *_: (b, iq, 0)),
        pl.BlockSpec((1, 1, st.bk), lambda b, h, iq, ik, _, kv_at: (b, 0, at(b, iq, ik, kv_at))),
    )


def _forward(q, k, v, ids, st):
    B, H, Sq, dh = q.shape
    K, Sk = k.shape[1], k.shape[2]
    nq, nk, G = Sq // st.bq, Sk // st.bk, H // K
    seg_q, seg_k, pos_q, pos_k = ids
    ok = _table(ids, st)
    q_spec, kv_spec, row_q, col_q, row_k = _q_major_specs(nq, nk, G, dh, st)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, col_q, row_k, col_q, row_k],
        out_specs=[q_spec, row_q],
        scratch_shapes=[
            pltpu.VMEM((st.bq, dh), _F32),  # acc
            pltpu.VMEM((st.bq, 1), _F32),  # running max
            pltpu.VMEM((st.bq, 1), _F32),  # running sum
        ],
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, st=st, nq=nq, nk=nk),
        name="packed_attn_fwd",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, Sq), _F32)],
        compiler_params=_SEMANTICS,
        interpret=st.interpret,
    )(ok.reshape(-1), _fetch(ok), q, k, v, _col(seg_q), _row(seg_k), _col(pos_q), _row(pos_k))


def _backward_dq(q, k, v, do, lse, delta, ids, st):
    B, H, Sq, dh = q.shape
    K, Sk = k.shape[1], k.shape[2]
    nq, nk, G = Sq // st.bq, Sk // st.bk, H // K
    seg_q, seg_k, pos_q, pos_k = ids
    ok = _table(ids, st)
    q_spec, kv_spec, row_q, col_q, row_k = _q_major_specs(nq, nk, G, dh, st)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_q, row_q, col_q, row_k, col_q, row_k],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((st.bq, dh), _F32)],
    )
    return pl.pallas_call(
        functools.partial(_dq_kernel, st=st, nq=nq, nk=nk),
        name="packed_attn_dq",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_SEMANTICS,
        interpret=st.interpret,
    )(ok.reshape(-1), _fetch(ok), q, k, v, do, lse, delta,
      _col(seg_q), _row(seg_k), _col(pos_q), _row(pos_k))


def _backward_dkv(q, k, v, do, lse, delta, ids, st):
    B, H, Sq, dh = q.shape
    K, Sk = k.shape[1], k.shape[2]
    nq, nk, G = Sq // st.bq, Sk // st.bk, H // K
    seg_q, seg_k, pos_q, pos_k = ids
    ok = _table(ids, st)
    # the query heads of KV head kv are rows kv*G .. kv*G+G-1: one KV head's
    # queries as one (G * Sq)-long sequence, a free reshape
    group = lambda x: x.reshape(B, K, G * Sq, dh)
    group_row = lambda x: x.reshape(B, K, 1, G * Sq)
    at = lambda b, ik, j, q_at: q_at[(b * nk + ik) * G * nq + j]
    q_spec = pl.BlockSpec((1, 1, st.bq, dh),
                          lambda b, kv, ik, j, _, q_at: (b, kv, at(b, ik, j, q_at), 0))
    kv_spec = pl.BlockSpec((1, 1, st.bk, dh), lambda b, kv, ik, j, *_: (b, kv, ik, 0))
    row_q = pl.BlockSpec((1, 1, 1, st.bq),
                         lambda b, kv, ik, j, _, q_at: (b, kv, 0, at(b, ik, j, q_at)))
    ids_q = pl.BlockSpec((1, 1, st.bq), lambda b, kv, ik, j, _, q_at: (b, 0, at(b, ik, j, q_at) % nq))
    ids_k = pl.BlockSpec((1, st.bk, 1), lambda b, kv, ik, j, *_: (b, ik, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, nk, G * nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_q, row_q, ids_q, ids_k, ids_q, ids_k],
        out_specs=[kv_spec, kv_spec],
        scratch_shapes=[pltpu.VMEM((st.bk, dh), _F32), pltpu.VMEM((st.bk, dh), _F32)],
    )
    return pl.pallas_call(
        functools.partial(_dkv_kernel, st=st, nq=nq, nk=nk, n_inner=G * nq),
        name="packed_attn_dkv",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_SEMANTICS,
        interpret=st.interpret,
    )(ok.reshape(-1), _fetch(jnp.tile(ok.transpose(0, 2, 1), (1, 1, G))),
      group(q), k, v, group(do), group_row(lse), group_row(delta),
      _row(seg_q), _col(seg_k), _row(pos_q), _col(pos_k))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _attention(q, k, v, ids, st):
    return _forward(q, k, v, ids, st)[0]


def _attention_fwd(q, k, v, ids, st):
    o, lse = _forward(q, k, v, ids, st)
    return o, (q, k, v, o, lse, ids)


def _attention_bwd(st, res, do):
    q, k, v, o, lse, ids = res
    delta = jnp.sum(o.astype(_F32) * do.astype(_F32), axis=-1)[:, :, None, :]
    dq = _backward_dq(q, k, v, do, lse, delta, ids, st)
    dk, dv = _backward_dkv(q, k, v, do, lse, delta, ids, st)
    return dq, dk, dv, None


_attention.defvjp(_attention_fwd, _attention_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"),
)
def packed_flash_attention(q, k, v, seg_q, seg_k, pos_q, pos_k, *,
                           causal=True, window=None, scale=None,
                           block_q=BLOCK_Q, block_k=BLOCK_K, interpret=False):
    """q (B,Sq,H,dh); k/v (B,Sk,K,dh) -> (B,Sq,H,dh). See module docstring."""
    B, Sq, H, dh = q.shape
    Sk = k.shape[1]
    if scale is None:
        scale = dh ** -0.5
    st = _Static(causal, window, float(scale), min(block_q, Sq), min(block_k, Sk), interpret)

    # pad sequence dims to block multiples (padding has seg id 0 => masked)
    def pad_to(x, mult):
        pad = (-x.shape[1]) % mult
        if pad == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[1] = (0, pad)
        return jnp.pad(x, widths)

    ids = (pad_to(seg_q, st.bq), pad_to(seg_k, st.bk),
           pad_to(pos_q, st.bq), pad_to(pos_k, st.bk))
    heads_major = lambda x, mult: pad_to(x, mult).transpose(0, 2, 1, 3)
    out = _attention(heads_major(q, st.bq), heads_major(k, st.bk), heads_major(v, st.bk),
                     ids, st)
    return out.transpose(0, 2, 1, 3)[:, :Sq]


def skipped_block_fraction(seg, pos, bq, bk, *, causal=True, window=None):
    """Fraction of (q,k) tiles skipped for a packed batch — the measured
    counterpart of the paper's sum(l^2)/N^2 ratio."""
    meta = block_metadata(seg, seg, pos, pos, bq, bk, causal=causal, window=window)
    return 1.0 - float(meta.mean())


def tile_counts(seg, *, block_q=BLOCK_Q, block_k=BLOCK_K):
    """(tiles, tiles that run) of one causal self-attention call over a host
    batch of segment ids (B, S), at the kernel's block sizes and with the
    absolute positions the model passes; numpy only, no device work."""
    seg = np.asarray(seg)
    S = seg.shape[1]
    bq, bk = min(block_q, S), min(block_k, S)

    def padded(b):  # as the kernel pads: segment id 0
        ids = np.pad(seg, ((0, 0), (0, (-S) % b)))
        return ids, np.broadcast_to(np.arange(ids.shape[1], dtype=np.int32), ids.shape)

    (seg_q, pos_q), (seg_k, pos_k) = padded(bq), padded(bk)
    meta = block_metadata(seg_q, seg_k, pos_q, pos_k, bq, bk, causal=True, window=None, xp=np)
    return int(meta.size), int(meta.sum())
