"""Device milliseconds per step of the operations whose innermost program
scope is `attn_proj` (models/attention.py: q/k/v/o, qk-norm, RoPE),
over the traced window's steps."""
from chipbench.layer_metrics import scope_ms


def read(ctx):
    return scope_ms(ctx, "attn_proj")
