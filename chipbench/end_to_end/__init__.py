"""End-to-end metrics, one reader per metric, named by the metric; each has
`read(ctx) -> float | None` over the untraced run (see layer_metrics)."""
