"""The pieces of a training step's work that model types share, from shapes
and each row's segment ids. Recomputation is not counted. Each arch module's
`work` puts them together for its model type.

- Matmul FLOPs: 6 per matmul parameter per document token (2 forward, 4
  backward), over the layers' projections and the lm head. The embedding is
  a gather, not a matmul.
- Attention: each document of length l attends causally within itself, so
  it needs P = l (l + 1) / 2 query-key pairs per head. Forward is
  2 P (dh_qk + dh_v) FLOPs per query head (q k^T and p v), backward twice
  that. Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o
  and dO and writes dq, dk, dv, all bfloat16:
  (3 H + 3 K) (dh_qk + dh_v) l 2 bytes.

The arguments are sizes, not configuration keys: D hidden, F feed-forward,
H query and K key/value heads, dh_qk the query/key and dh_v the value head
size, L layers, V (padded) vocabulary rows.
"""
from __future__ import annotations

import numpy as np

BF16_BYTES = 2


def doc_lengths(segment_ids) -> np.ndarray:
    """Lengths of all documents in a (rows, seq) segment-id array."""
    out = []
    for row in np.asarray(segment_ids):
        counts = np.bincount(row[row > 0])
        out.append(counts[counts > 0])
    return np.concatenate(out).astype(np.int64) if out else np.zeros(0, np.int64)


def doc_tokens(segment_ids) -> int:
    """Tokens that belong to a document (segment id > 0)."""
    return int(np.sum(np.asarray(segment_ids) > 0))


def causal_pairs(segment_ids) -> int:
    """Causal query-key pairs within each document, over all rows."""
    lens = doc_lengths(segment_ids)
    return int(np.sum(lens * (lens + 1) // 2))


def gqa_matmul_params(D, F, H, K, dh_qk, dh_v, L, V) -> int:
    """Matmul parameters of L layers of grouped-query attention and a gated
    FFN, and the lm head."""
    per_layer = D * H * dh_qk + D * K * dh_qk + D * K * dh_v + H * dh_v * D + 3 * D * F
    return L * per_layer + D * V


def attention_work(segment_ids, H, K, dh_qk, dh_v, L):
    """(FLOPs, bytes) causal per-document attention needs for one step over
    L layers, forward and backward."""
    flops = 6 * causal_pairs(segment_ids) * H * (dh_qk + dh_v) * L
    nbytes = (3 * H + 3 * K) * (dh_qk + dh_v) * doc_tokens(segment_ids) * BF16_BYTES * L
    return flops, nbytes
