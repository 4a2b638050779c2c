"""Tokens that carry a loss target (labels >= 0) over the whole window: from
the start of its first step to the end of the last, stalls and re-plans
included."""


def read(ctx):
    seconds = ctx.steps[-1]["t1"] - ctx.steps[0]["t0"]
    return sum(s["tokens"] for s in ctx.steps) / seconds
