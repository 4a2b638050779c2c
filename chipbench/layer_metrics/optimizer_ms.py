"""Device milliseconds per step of the operations whose innermost program
scope is `optimizer` (train/: gradient scale, global norm, clip, update),
over the traced window's steps."""
from chipbench.layer_metrics import scope_ms


def read(ctx):
    return scope_ms(ctx, "optimizer")
