"""Device milliseconds per step of the operations whose innermost program
scope is `lm_head` (models/model.py: final norm, logits, log-softmax, z-loss, nll),
over the traced window's steps."""
from chipbench.layer_metrics import scope_ms


def read(ctx):
    return scope_ms(ctx, "lm_head")
