"""Pallas packed flash attention vs the pure-jnp oracle (interpret mode):
shape/dtype sweeps, GQA ratios, windows, property-based packing layouts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _ht import given, settings, strategies as st

from repro.kernels.ops import packed_attention
from repro.kernels.packed_flash_attn import block_metadata, skipped_block_fraction
from repro.kernels.ref import packed_attention_ref

from conftest import make_packed


def _qkv(rng, B, S, H, K, dh, dtype):
    q = jnp.asarray(rng.normal(size=(B, S, H, dh)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, K, dh)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, K, dh)), dtype)
    return q, k, v


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("S,H,K,dh,bq,bk", [
    (128, 4, 4, 32, 64, 64),    # MHA
    (128, 4, 2, 32, 64, 64),    # GQA 2:1
    (256, 8, 1, 16, 128, 128),  # MQA
    (192, 4, 4, 64, 64, 64),    # non-power-of-two block count + padding
    (128, 4, 4, 32, 32, 64),    # bq != bk
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_ref(rng, S, H, K, dh, bq, bk, dtype):
    B = 2
    q, k, v = _qkv(rng, B, S, H, K, dh, dtype)
    seg, pos = make_packed(rng, B, S)
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    out = packed_attention(q, k, v, seg, seg, pos, pos, causal=True,
                           block_q=bq, block_k=bk)
    ref = packed_attention_ref(q, k, v, seg, seg, pos, pos, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window", [16, 64, None])
def test_kernel_window(rng, window):
    B, S, H, K, dh = 1, 128, 2, 2, 32
    q, k, v = _qkv(rng, B, S, H, K, dh, jnp.float32)
    seg, pos = make_packed(rng, B, S, doc_lens=[S])
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    out = packed_attention(q, k, v, seg, seg, pos, pos, causal=True,
                           window=window, block_q=32, block_k=32)
    ref = packed_attention_ref(q, k, v, seg, seg, pos, pos, causal=True,
                               window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_kernel_padding_rows_zero(rng):
    """Rows with segment id 0 (padding) must return exactly 0."""
    B, S, H, dh = 1, 64, 2, 16
    q, k, v = _qkv(rng, B, S, H, H, dh, jnp.float32)
    seg = np.zeros((B, S), np.int32)
    seg[:, :40] = 1
    pos = np.arange(S, dtype=np.int32)[None] * (seg > 0)
    out = packed_attention(q, k, v, jnp.asarray(seg), jnp.asarray(seg),
                           jnp.asarray(pos), jnp.asarray(pos),
                           causal=True, block_q=32, block_k=32)
    assert bool(jnp.all(out[:, 40:] == 0))


@settings(max_examples=12, deadline=None)
@given(
    doc_split=st.lists(st.integers(8, 64), min_size=1, max_size=5),
    hk=st.sampled_from([(4, 4), (4, 2), (8, 1)]),
)
def test_kernel_property_random_packing(doc_split, hk):
    H, K = hk
    rng = np.random.default_rng(sum(doc_split))
    S = 128
    q = jnp.asarray(rng.normal(size=(1, S, H, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, S, K, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, S, K, 16)), jnp.float32)
    seg, pos = make_packed(rng, 1, S, doc_lens=doc_split)
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    out = packed_attention(q, k, v, seg, seg, pos, pos, causal=True,
                           block_q=32, block_k=32)
    ref = packed_attention_ref(q, k, v, seg, seg, pos, pos, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


def test_block_skipping_reflects_sum_l2(rng):
    """More, shorter documents => more skipped tiles (the sum l_i^2 effect)."""
    S = 512
    seg1, pos1 = make_packed(rng, 1, S, doc_lens=[S])  # one long doc
    seg4, pos4 = make_packed(rng, 1, S, doc_lens=[S // 4] * 4)
    f1 = skipped_block_fraction(jnp.asarray(seg1), jnp.asarray(pos1), 64, 64)
    f4 = skipped_block_fraction(jnp.asarray(seg4), jnp.asarray(pos4), 64, 64)
    assert f4 > f1
    # 4 equal docs: visible work ~ 4 * (S/4)^2 / S^2 = 1/4 of one-doc's lower
    # triangle; tile-granularity makes it approximate
    assert f4 - f1 > 0.25


def test_block_metadata_never_skips_needed_tiles(rng):
    """Safety: every (q,k) pair visible under the exact mask lies in a tile
    with blk_ok == 1 (skipping is conservative)."""
    S, bq, bk = 128, 32, 32
    seg, pos = make_packed(rng, 1, S)
    segj, posj = jnp.asarray(seg), jnp.asarray(pos)
    meta = np.asarray(block_metadata(segj, segj, posj, posj, bq, bk,
                                     causal=True, window=None))[0]
    mask = (seg[0][:, None] == seg[0][None, :]) & (seg[0][:, None] != 0)
    mask &= pos[0][:, None] >= pos[0][None, :]
    for iq in range(S // bq):
        for ik in range(S // bk):
            tile = mask[iq * bq:(iq + 1) * bq, ik * bk:(ik + 1) * bk]
            if tile.any():
                assert meta[iq, ik] == 1


def _grads(attn, q, k, v, w):
    """d/d(q, k, v) of sum(attn(q, k, v) * w), in float32."""
    loss = lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w)
    return [g.astype(jnp.float32) for g in jax.grad(loss, argnums=(0, 1, 2))(q, k, v)]


# each case: (H, K) query and KV heads, causal, window, Sq, Sk, blocks, the
# documents of every row (None: random packing), padded tail, dtype
GRAD_CASES = {
    "gqa1-causal-f32": ((4, 4), True, None, 128, 128, (32, 32), None, 0, jnp.float32),
    "gqa4-causal-bf16": ((8, 2), True, None, 128, 128, (32, 64), None, 0, jnp.bfloat16),
    "gqa8-causal-f32": ((8, 1), True, None, 128, 128, (64, 32), None, 0, jnp.float32),
    "gqa1-noncausal-bf16": ((4, 4), False, None, 128, 128, (32, 32), None, 0, jnp.bfloat16),
    "gqa4-noncausal-f32": ((8, 2), False, None, 128, 128, (32, 32), None, 0, jnp.float32),
    "gqa8-noncausal-bf16": ((8, 1), False, None, 128, 128, (32, 32), None, 0, jnp.bfloat16),
    "window16-gqa4-f32": ((8, 2), True, 16, 128, 128, (32, 32), [128], 0, jnp.float32),
    "window16-gqa1-bf16": ((4, 4), True, 16, 128, 128, (16, 32), [70, 58], 0, jnp.bfloat16),
    "sq-ne-sk-cross-f32": ((8, 2), False, None, 64, 96, (32, 32), None, 0, jnp.float32),
    "sq-ne-sk-cross-bf16": ((4, 1), False, None, 96, 160, (32, 64), None, 0, jnp.bfloat16),
    "padding-gqa4-f32": ((8, 2), True, None, 128, 128, (32, 32), [30, 45], 53, jnp.float32),
    "padding-window16-bf16": ((4, 2), True, 16, 160, 160, (32, 32), [50, 41], 69, jnp.bfloat16),
    "straddle-gqa8-f32": ((8, 1), True, None, 128, 128, (32, 32), [20, 50, 30, 28], 0,
                          jnp.float32),
    "straddle-gqa4-bf16": ((8, 2), True, None, 192, 192, (64, 64), [17, 90, 33, 52], 0,
                           jnp.bfloat16),
}
# norm of the gradient's error over the reference's: float32 is exact up to
# summation order; bfloat16 rounds q, k, v, p and dS to 8 bits at the MXU
GRAD_TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("case", list(GRAD_CASES))
def test_kernel_gradients_match_ref(rng, case):
    (H, K), causal, window, Sq, Sk, (bq, bk), docs, pad, dtype = GRAD_CASES[case]
    B, dh = 2, 16
    q = jnp.asarray(rng.normal(size=(B, Sq, H, dh)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Sk, K, dh)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Sk, K, dh)), dtype)
    w = jnp.asarray(rng.normal(size=(B, Sq, H, dh)), jnp.float32)
    seg_q, pos_q = make_packed(rng, B, Sq - pad, doc_lens=docs)
    seg_k, pos_k = (seg_q, pos_q) if Sq == Sk else make_packed(rng, B, Sk, doc_lens=docs)
    ids = [jnp.asarray(np.pad(x, ((0, 0), (0, S - x.shape[1]))))
           for x, S in ((seg_q, Sq), (seg_k, Sk), (pos_q, Sq), (pos_k, Sk))]
    opts = dict(causal=causal, window=window)
    with jax.default_matmul_precision("highest"):
        got = _grads(lambda q, k, v: packed_attention(q, k, v, *ids, block_q=bq, block_k=bk,
                                                      **opts), q, k, v, w)
        want = _grads(lambda q, k, v: packed_attention_ref(q, k, v, *ids, **opts), q, k, v, w)
    for name, g, r in zip("qkv", got, want):
        assert g.shape == r.shape and bool(jnp.all(jnp.isfinite(g))), name
        err = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert err < GRAD_TOL[dtype], (name, err)
    if pad:  # padded queries, keys and values take no gradient
        for g, S in zip(got, (Sq, Sk, Sk)):
            assert bool(jnp.all(g[:, S - pad:] == 0))


def test_kernel_gradients_of_a_row_with_every_tile_skipped(rng):
    """Row 1 is all padding, so the tile table skips its every tile: it gets
    zero output and zero gradients, with no NaN; row 0 still matches."""
    B, S, H, K, dh = 2, 128, 4, 2, 16
    q, k, v = _qkv(rng, B, S, H, K, dh, jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    seg, pos = make_packed(rng, B, S)
    seg[1], pos[1] = 0, 0
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    meta = block_metadata(seg, seg, pos, pos, 32, 32, causal=True, window=None)
    assert int(meta[1].sum()) == 0 and int(meta[0].sum()) > 0
    attn = lambda q, k, v: packed_attention(q, k, v, seg, seg, pos, pos, block_q=32, block_k=32)
    out = attn(q, k, v)
    got = _grads(attn, q, k, v, w)
    want = _grads(lambda q, k, v: packed_attention_ref(q, k, v, seg, seg, pos, pos), q, k, v, w)
    assert bool(jnp.all(out[1] == 0))
    for g, r in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(g)))
        assert bool(jnp.all(g[1] == 0))
        np.testing.assert_allclose(np.asarray(g[0]), np.asarray(r[0]), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("window", [16, 40])
def test_block_metadata_never_skips_needed_tiles_in_a_window(rng, window):
    """Safety under a sliding window with per-document positions, which
    restart inside a tile: every visible (q, k) pair lies in a tile that runs."""
    S, bq, bk = 128, 16, 32
    seg, pos = make_packed(rng, 2, S, doc_lens=[70, 58])
    segj, posj = jnp.asarray(seg), jnp.asarray(pos)
    meta = np.asarray(block_metadata(segj, segj, posj, posj, bq, bk, causal=True, window=window))
    for b in range(2):
        mask = (seg[b][:, None] == seg[b][None, :]) & (seg[b][:, None] != 0)
        mask &= (pos[b][:, None] >= pos[b][None, :]) & (pos[b][:, None] - pos[b][None, :] < window)
        for iq in range(S // bq):
            for ik in range(S // bk):
                if mask[iq * bq:(iq + 1) * bq, ik * bk:(ik + 1) * bk].any():
                    assert meta[b, iq, ik] == 1, (b, iq, ik)
