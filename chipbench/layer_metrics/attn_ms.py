"""Device milliseconds per step of the operations under the program's
`attn_core` scope (models/attention.py: the attention core, on TPU the
Pallas kernels), over the traced window's steps."""
from chipbench.layer_metrics import scope_ms


def read(ctx):
    return scope_ms(ctx, "attn_core")
