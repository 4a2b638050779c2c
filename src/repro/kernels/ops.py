"""Public jit'd wrapper over the Pallas packed flash attention kernels.

Dispatch: on the CPU backend the kernels run in interpret mode (same kernels,
Python evaluation), so values and gradients are validated end to end without
a chip; on every other backend they are compiled natively. The wrapper is
differentiable in q, k and v through the kernels' own backward pass.
"""
from __future__ import annotations

import jax

from repro.kernels.packed_flash_attn import (  # noqa: F401
    BLOCK_K,
    BLOCK_Q,
    block_metadata,
    packed_flash_attention,
    skipped_block_fraction,
)
from repro.kernels.ref import packed_attention_ref  # noqa: F401


def packed_attention(q, k, v, seg_q, seg_k, pos_q, pos_k, *, causal=True,
                     window=None, scale=None, block_q=BLOCK_Q, block_k=BLOCK_K):
    """Segment-aware flash attention; interpret mode on CPU only."""
    return packed_flash_attention(
        q, k, v, seg_q, seg_k, pos_q, pos_k,
        causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k,
        interpret=jax.default_backend() == "cpu",
    )
