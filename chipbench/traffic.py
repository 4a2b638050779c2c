"""The one traffic generator: reads a mix from traffic/<mix>.json and makes
every step's batch from (seed, step) alone.

A mix gives the document-length distribution and the packing; the
configuration gives the row length, the rows per step and the vocabulary the
token ids are drawn from. The document lengths of step i come from (the
mix's sizes_seed, i), the same for every run seed, so that every seed does
the same work in the same order; the run's seed draws the token ids.

Lengths are drawn as `repro.data.synth.SyntheticPackedDataset.batch_at`
draws them (copied here, so that a change to the program cannot move the
yardstick): lognormal, clipped to [min, max_rows * row], as many as that
dataset draws for a step, then eight more at a time while the step's rows
are not yet covered. The packing is a pretraining packer's with carry-over
("carry_over"): the documents are laid end to end and cut into rows, so that
every row is full; a document cut at a row's end goes on at the start of the
next row as a new segment (its positions start again at 0), and the last
document is cut where the step's rows end. Token ids are random in
[1, vocab); a label is the next id within a segment, and -1 at a segment's
last position.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def seed_entropy(seed: int) -> int:
    """Any whole number (negative or above 64 bits too) -> entropy for numpy."""
    return int(seed) % 2**128


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def carry_over(doc_lengths, seq_len, rows):
    """Documents laid end to end and cut into `rows` rows of seq_len: each
    row's segment lengths. The lengths must cover rows * seq_len."""
    out, row, free = [], [], seq_len
    for n in doc_lengths:
        n = int(n)
        while n > 0 and len(out) < rows:
            take = min(n, free)
            row.append(take)
            n, free = n - take, free - take
            if free == 0:
                out.append(row)
                row, free = [], seq_len
    if len(out) < rows:
        raise ValueError("the documents do not cover the rows")
    return out


def row_arrays(row, seq_len, rng, vocab):
    """One packed row -> (tokens, segment_ids, positions, labels)."""
    tokens = np.zeros(seq_len, np.int32)
    seg = np.zeros(seq_len, np.int32)
    pos = np.zeros(seq_len, np.int32)
    off = 0
    for i, n in enumerate(row):
        tokens[off:off + n] = rng.integers(1, vocab, size=n)
        seg[off:off + n] = i + 1
        pos[off:off + n] = np.arange(n)
        off += n
    labels = np.where(seg > 0, np.roll(tokens, -1), -1).astype(np.int32)
    labels[np.roll(seg, -1) != seg] = -1  # never across a boundary or into padding
    labels[-1] = -1  # nor round from the row's end to its start
    return tokens, seg, pos, labels


class Traffic:
    """Batches of one mix for one configuration and seed."""

    def __init__(self, mix: dict, seq_len: int, rows: int, vocab: int, seed: int):
        self.mix, self.seq_len, self.rows, self.vocab = mix, seq_len, rows, vocab
        self.entropy = seed_entropy(seed)

    def _lengths(self, rng, n):
        d = self.mix["doc_lengths"]
        if d["dist"] == "lognormal":
            lens = rng.lognormal(mean=d["mu"], sigma=d["sigma"], size=n)
            return np.clip(lens, d["min"], d["max_rows"] * self.seq_len).astype(np.int64)
        if d["dist"] == "fixed":
            return np.full(n, d["rows"] * self.seq_len, np.int64)
        raise ValueError(f"unknown length distribution {d['dist']!r}")

    def batch(self, step: int) -> dict:
        """Step `step`'s batch: (rows, seq_len) int32 arrays."""
        if self.mix["packing"] != "carry_over":
            raise ValueError(f"unknown packing {self.mix['packing']!r}")
        rng = np.random.default_rng((self.mix["sizes_seed"], step))
        ids = np.random.default_rng((self.entropy, step))
        d = self.mix["doc_lengths"]
        if d["dist"] == "lognormal":
            mean = np.exp(d["mu"] + d["sigma"] ** 2 / 2)
            n_docs = max(8, int(self.rows * self.seq_len / mean * 0.9))
        else:
            n_docs = self.rows
        lens = [self._lengths(rng, n_docs)]
        while sum(int(x.sum()) for x in lens) < self.rows * self.seq_len:
            lens.append(self._lengths(rng, 8))
        rows = carry_over(np.concatenate(lens), self.seq_len, self.rows)
        out = [row_arrays(r, self.seq_len, ids, self.vocab) for r in rows]
        return {k: np.stack([o[i] for o in out])
                for i, k in enumerate(("tokens", "segment_ids", "positions", "labels"))}
