"""Device milliseconds per step of the operations under the program's
`attn_core` scope (models/attention.py), over the traced window's steps."""


def read(ctx):
    if ctx.trace is None or not ctx.steps:
        return None
    seconds = ctx.trace["scope_s"].get("attn_core", 0.0)
    if seconds <= 0:
        return None
    return 1e3 * seconds / len(ctx.steps)
