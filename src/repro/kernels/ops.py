"""Public jit'd wrapper over the Pallas packed flash attention kernel.

Dispatch: on the CPU backend the kernel body runs in interpret mode (same
kernel, Python evaluation), so correctness is validated end to end without a
chip; on every other backend it is compiled natively.
"""
from __future__ import annotations

import jax

from repro.kernels.packed_flash_attn import (  # noqa: F401
    block_metadata,
    packed_flash_attention,
    skipped_block_fraction,
)
from repro.kernels.ref import packed_attention_ref  # noqa: F401


def packed_attention(q, k, v, seg_q, seg_k, pos_q, pos_k, *, causal=True,
                     window=None, scale=None, block_q=128, block_k=128):
    """Segment-aware flash attention; interpret mode on CPU only."""
    return packed_flash_attention(
        q, k, v, seg_q, seg_k, pos_q, pos_k,
        causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k,
        interpret=jax.default_backend() == "cpu",
    )
