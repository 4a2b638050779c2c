"""Work a training step needs, from the configuration's shapes and each
row's segment ids. Recomputation is not counted.

- Matmul FLOPs: 6 per matmul parameter per document token (2 forward, 4
  backward), over the layers' projections and the lm head. The embedding is
  a gather, not a matmul.
- Attention: each document of length l attends causally within itself, so
  it needs P = l (l + 1) / 2 query-key pairs per head. Forward is
  4 P head_dim FLOPs per query head (q k^T and p v), backward twice that.
  Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o and
  dO and writes dq, dk, dv, all bfloat16: (6 H + 6 K) l head_dim 2 bytes.
"""
from __future__ import annotations

import numpy as np

BF16_BYTES = 2


def dims(cfg):
    return (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_hidden_layers"],
            cfg["assumed"]["padded_vocab_size"])


def matmul_params(cfg) -> int:
    D, F, H, K, dh, L, V = dims(cfg)
    per_layer = D * H * dh + 2 * D * K * dh + H * dh * D + 3 * D * F
    return L * per_layer + D * V


def doc_lengths(segment_ids) -> np.ndarray:
    """Lengths of all documents in a (rows, seq) segment-id array."""
    out = []
    for row in np.asarray(segment_ids):
        counts = np.bincount(row[row > 0])
        out.append(counts[counts > 0])
    return np.concatenate(out).astype(np.int64) if out else np.zeros(0, np.int64)


def attention_work(cfg, segment_ids):
    """(FLOPs, bytes) attention needs for one step, all layers, fwd + bwd."""
    _, _, H, K, dh, L, _ = dims(cfg)
    lens = doc_lengths(segment_ids)
    pairs = int(np.sum(lens * (lens + 1) // 2))
    flops = 12 * pairs * H * dh * L
    nbytes = (6 * H + 6 * K) * int(lens.sum()) * dh * BF16_BYTES * L
    return flops, nbytes


def step_flops(cfg, segment_ids) -> int:
    """Model FLOPs one step needs: matmuls over document tokens plus attention."""
    tokens = int(np.sum(np.asarray(segment_ids) > 0))
    return 6 * matmul_params(cfg) * tokens + attention_work(cfg, segment_ids)[0]
