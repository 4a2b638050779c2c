"""Model FLOP/s utilization of the whole step: the FLOPs the window's steps
need (flops.step_flops: matmuls and causal per-document attention, forward
and backward, no recomputation) over the window's host-clock time, the
cell's chips and each chip's bf16 peak. Percent."""


def read(ctx):
    steps = ctx.steps
    if not steps:
        return None
    seconds = steps[-1]["t1"] - steps[0]["t0"]
    need = sum(s["flops"] for s in steps)
    return 100.0 * need / (seconds * ctx.chips * ctx.peaks["bf16_flops_per_s"])
