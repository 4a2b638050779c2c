"""Attention's share of its roofline: the least time the chip could take
for the attention the packing mask needs (the arch module's `attn_flops`
and `attn_bytes`: causal per-document pairs, forward and backward, q/k/v/o
and their gradients read or written once in bf16), the larger of FLOPs over
peak FLOP/s and bytes over peak HBM bytes/s, over the device time of the
operations under `attn_core`. Percent. The same work is credited whatever
computes it."""
from chipbench.layer_metrics import scope_ms


def read(ctx):
    ms = scope_ms(ctx, "attn_core")
    if ms is None:
        return None
    flops = sum(s["attn_flops"] for s in ctx.steps)
    nbytes = sum(s["attn_bytes"] for s in ctx.steps)
    least = max(flops / ctx.peaks["bf16_flops_per_s"], nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (1e-3 * ms * len(ctx.steps))
