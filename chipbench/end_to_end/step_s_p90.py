"""90th percentile of the window's per-step wall times (numpy's linear
interpolation), each step from its batch's transfer to the block on its
new state."""
import numpy as np


def read(ctx):
    return float(np.percentile([s["t1"] - s["t0"] for s in ctx.steps], 90))
