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
