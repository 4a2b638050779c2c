"""Per-layer metrics, one reader per metric, named by the metric.

Each module has `read(ctx) -> float | None`. `ctx` carries the cell's
configuration (`cfg`), its chips (`chips`) and their peaks (`peaks`), the
window's step records (`steps`, host clock, with the counts its arch
module's `work` gives) and the reduced trace (`trace`, see trace.reduce). A
reader that finds nothing to read returns None, and the metric is left out
of the line.
"""


def scope_ms(ctx, name):
    """Device milliseconds per window step of the operations whose innermost
    program scope is `name` (trace.reduce's scope_s), or None where the trace
    holds none."""
    if ctx.trace is None or not ctx.steps:
        return None
    seconds = ctx.trace["scope_s"].get(name, 0.0)
    if seconds <= 0:
        return None
    return 1e3 * seconds / len(ctx.steps)
