"""The benchmark's own counts: FLOPs and bytes from shapes (the shared
formulas and each arch module's `work`), the table of peaks, and the
traffic generator."""
import json

import numpy as np
import pytest

from chipbench import device, flops
from chipbench.archs import qwen3
from chipbench.tests import tiny
from chipbench.traffic import Traffic, load_mix

CFG_1CHIP = json.loads((tiny.CHIPBENCH / "configs" / "qwen3-8b-1chip.json").read_text())


def test_matmul_params_of_qwen3_8b_one_chip_cut():
    D, F, H, K, dh, V = 4096, 12288, 32, 8, 128, 19200
    layer = D * H * dh + 2 * D * K * dh + H * dh * D + 3 * D * F
    assert layer == 192_937_984 and D * V == 78_643_200
    L = CFG_1CHIP["num_hidden_layers"]
    assert flops.gqa_matmul_params(D, F, H, K, dh, dh, L, V) == L * layer + D * V
    assert flops.gqa_matmul_params(D, F, H, K, dh, dh, 1, V) == 271_581_184
    # a value head size of its own (MLA's 192/128) counts k and v apart
    assert (flops.gqa_matmul_params(D, F, H, K, 192, 128, 1, V) - 271_581_184
            == D * H * 64 + D * K * 64)


def test_step_flops_are_six_per_parameter_per_document_token_plus_attention():
    seg = np.zeros((2, 4096), np.int32)
    seg[0, :4000] = 1
    seg[1, :100], seg[1, 100:300] = 1, 2
    tokens = 4000 + 300
    w = qwen3.work(dict(CFG_1CHIP, num_hidden_layers=1), seg)
    attn, nbytes = flops.attention_work(seg, 32, 8, 128, 128, 1)
    assert (w["attn_flops"], w["attn_bytes"]) == (attn, nbytes)
    assert w["flops"] == 6 * 271_581_184 * tokens + attn


def _brute_force(seg, H, K, dh_qk, dh_v, L):
    """Count the causal same-document pairs one query at a time: forward
    q k^T (2 dh_qk) and p v (2 dh_v) a pair and head, backward twice that;
    q, k, v, o each read or written three times, in bf16."""
    pairs, tokens = 0, 0
    for row in seg:
        for i in range(len(row)):
            if row[i] == 0:
                continue
            tokens += 1
            pairs += sum(1 for j in range(i + 1) if row[j] == row[i])
    fwd = pairs * H * (2 * dh_qk + 2 * dh_v)
    moved = 3 * (H * dh_qk + K * dh_qk + K * dh_v + H * dh_v)
    return 3 * fwd * L, moved * tokens * 2 * L


@pytest.mark.parametrize("dh_qk,dh_v", [(128, 128), (192, 128)])
@pytest.mark.parametrize("lens", [[5, 3, 8], [16], [1, 1, 2, 7], [9, 6]])
def test_attention_work_matches_a_brute_force_count(lens, dh_qk, dh_v):
    seg = np.zeros((2, 16), np.int32)
    off = 0
    for i, n in enumerate(lens):
        seg[0, off:off + n] = i + 1
        off += n
    seg[1, 2:6] = 1  # a second row with leading padding
    assert flops.attention_work(seg, 32, 8, dh_qk, dh_v, 3) == _brute_force(seg, 32, 8, dh_qk, dh_v, 3)


def _parents_count(cfg, seg):
    """qwen3's step work as the benchmark counted it before work was counted
    by model type: (flops, attn_flops, attn_bytes)."""
    D, F, H, K, dh, L = (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"],
                         cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_hidden_layers"])
    V = cfg["assumed"]["padded_vocab_size"]
    params = L * (D * H * dh + 2 * D * K * dh + H * dh * D + 3 * D * F) + D * V
    lens = flops.doc_lengths(seg)
    pairs = int(np.sum(lens * (lens + 1) // 2))
    attn_flops = 12 * pairs * H * dh * L
    attn_bytes = (6 * H + 6 * K) * int(lens.sum()) * dh * 2 * L
    tokens = int(np.sum(np.asarray(seg) > 0))
    return 6 * params * tokens + attn_flops, attn_flops, attn_bytes


def test_qwen3_work_is_the_parents_count_for_the_cells_first_batches():
    from chipbench.runners import common

    tr = CFG_1CHIP["train"]
    traffic = Traffic(load_mix("packed"), tr["seq_len"], tr["global_batch"],
                      CFG_1CHIP["vocab_size"], seed=2**33 + 5)
    cfg = dict(CFG_1CHIP, name="qwen3-8b-1chip")
    for step in range(12):
        batch = traffic.batch(step)
        w = common.step_work(cfg, batch)
        assert (w["flops"], w["attn_flops"], w["attn_bytes"]) == _parents_count(
            cfg, batch["segment_ids"]), step
        assert all(type(w[k]) is int for k in ("flops", "attn_flops", "attn_bytes"))


def test_peaks_refuse_an_unknown_device_kind():
    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        device.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("rows,seq", [(4, 512), (2, 4096)])
def test_traffic_is_deterministic_per_seed_with_the_same_sizes_for_every_seed(rows, seq):
    a = Traffic(load_mix("packed"), seq, rows, 1000, seed=2**40 + 3)
    b = Traffic(load_mix("packed"), seq, rows, 1000, seed=2**40 + 3)
    c = Traffic(load_mix("packed"), seq, rows, 1000, seed=2**40 + 4)
    for step in (0, 7):
        x, y, z = a.batch(step), b.batch(step), c.batch(step)
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
        assert not np.array_equal(x["tokens"], z["tokens"])
        for k in ("segment_ids", "positions"):
            np.testing.assert_array_equal(x[k], z[k])
        np.testing.assert_array_equal(x["labels"] >= 0, z["labels"] >= 0)
        assert x["tokens"].shape == (rows, seq) and x["tokens"].dtype == np.int32
        assert x["tokens"].max() < 1000


def _segments(row):
    """Lengths of a row's segments, in order."""
    cuts = np.flatnonzero(np.diff(row)) + 1
    return [len(p) for p in np.split(row, cuts)]


@pytest.mark.parametrize("seed", [0, 17, 2**31 + 1])
def test_packed_sizes_match_the_programs_synthetic_dataset(seed):
    """The document lengths SyntheticPackedDataset draws for the mix's
    sizes_seed, laid end to end and cut into full rows (carry-over); the
    token ids come from the run's seed."""
    from repro.data.synth import sample_doc_lengths

    mix = load_mix("packed")
    d = mix["doc_lengths"]
    S, B = 4096, 2
    ours = Traffic(mix, S, B, 18992, seed=seed)
    for step in (0, 1, 5):
        # as SyntheticPackedDataset.batch_at draws them, topped up eight at a time
        rng = np.random.default_rng((mix["sizes_seed"], step))
        n_docs = max(8, int(B * S / np.exp(d["mu"] + d["sigma"] ** 2 / 2) * 0.9))
        draw = lambda n: list(sample_doc_lengths(rng, n, S, mu=d["mu"], sigma=d["sigma"],
                                                 min_len=d["min"]))
        lens = draw(n_docs)
        while sum(lens) < B * S:
            lens += draw(8)
        # the stream cut at every document's end and every row's end
        cuts = sorted({int(c) for c in np.cumsum(lens) if c < B * S} | {S * r for r in range(B + 1)})
        want = [[b - a for a, b in zip(cuts, cuts[1:]) if r * S <= a < (r + 1) * S]
                for r in range(B)]
        a = ours.batch(step)
        assert [_segments(row) for row in a["segment_ids"]] == want, f"step {step}"
        assert (a["segment_ids"] > 0).all()  # every row full
        for row_seg, row_pos in zip(a["segment_ids"], a["positions"]):
            for s in np.unique(row_seg):
                np.testing.assert_array_equal(row_pos[row_seg == s], np.arange((row_seg == s).sum()))
        # a target at every position but a segment's last
        last = np.ones_like(a["segment_ids"], bool)
        last[:, :-1] = a["segment_ids"][:, :-1] != a["segment_ids"][:, 1:]
        np.testing.assert_array_equal(a["labels"] >= 0, ~last)
        np.testing.assert_array_equal(a["labels"][:, :-1][~last[:, :-1]],
                                      a["tokens"][:, 1:][~last[:, :-1]])
        assert a["tokens"].min() >= 1 and a["tokens"].max() < 18992


def test_a_document_cut_at_a_row_end_goes_on_in_the_next_row():
    from chipbench.traffic import carry_over

    assert carry_over([3, 10, 2, 9], 8, 3) == [[3, 5], [5, 2, 1], [8]]
    with pytest.raises(ValueError, match="cover"):
        carry_over([3, 4], 8, 1)
