"""Device milliseconds per step of the operations under no program scope
(the embedding's gather and scatter-add, XLA's copies and converts), over
the traced window's steps."""
from chipbench import trace
from chipbench.layer_metrics import scope_ms


def read(ctx):
    return scope_ms(ctx, trace.UNSCOPED)
