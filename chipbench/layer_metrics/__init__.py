"""Per-layer metrics, one reader per metric, named by the metric.

Each module has `read(ctx) -> float | None`. `ctx` carries the cell's
configuration (`cfg`), its chips (`chips`) and their peaks (`peaks`), the
window's step records (`steps`, host clock) and the reduced trace (`trace`,
see trace.reduce). A reader that finds nothing to read returns None, and the
metric is left out of the line.
"""
