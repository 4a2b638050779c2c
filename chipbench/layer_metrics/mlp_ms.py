"""Device milliseconds per step of the operations whose innermost program
scope is `mlp` (models/mlp.py: the dense gated FFN),
over the traced window's steps."""
from chipbench.layer_metrics import scope_ms


def read(ctx):
    return scope_ms(ctx, "mlp")
