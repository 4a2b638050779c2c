"""The comparison that decides `correct` for a training cell.

The program and the reference (reference/<model_type>.py, float32 at the
highest matmul precision) start from the same weights, drawn by the
benchmark from the seed, and take the same batches. Three numbers are read:

- loss_gap: the largest |program loss - reference loss| over the steps the
  reference follows.
- grad_norm_gap: for each leaf, the gap between the program's and the
  reference's norm of the first step's gradient as the optimizer gets it,
  over the reference's norm of that leaf or of the median leaf, whichever
  is larger; the worst leaf.
- change_gap: the same, for the norm of each leaf's change from the initial
  weights to the last step the reference follows. Leaves whose reference
  gradient is under a thousandth of the median leaf's move by round-off
  alone under Adam and are left out.

Each number has a limit in the configuration (`check.limits`); the run is
correct when every number is at or under its limit.
"""
from __future__ import annotations

import math

import numpy as np

NOUGHT_GRAD = 1e-3  # leaves under this share of the median gradient norm


def norm(x) -> float:
    return math.sqrt(float(np.sum(np.square(np.asarray(x), dtype=np.float64))))


def change_norms(final: dict, initial: dict) -> dict:
    return {k: norm(np.asarray(final[k], np.float32) - np.asarray(initial[k], np.float32))
            for k in initial}


def worst_leaf_gap(prog: dict, ref: dict, leaves=None) -> tuple[float, str]:
    leaves = sorted(ref) if leaves is None else sorted(leaves)
    median = float(np.median([ref[k] for k in ref]))
    worst, at = 0.0, ""
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
        if not math.isfinite(gap) or not math.isfinite(prog[k]):
            return math.inf, k
        if gap > worst:
            worst, at = gap, k
    return worst, at


def readings(prog, ref) -> dict:
    """prog, ref: dicts with 'losses', 'grad_norms' (leaf -> float) and
    'change_norms' (leaf -> float). -> {name: (value, worst leaf or step)}."""
    n = len(ref["losses"])
    gaps = [abs(a - b) for a, b in zip(prog["losses"][:n], ref["losses"])]
    if len(prog["losses"]) < n or not all(math.isfinite(g) for g in gaps):
        loss = (math.inf, "non-finite or missing loss")
    else:
        i = int(np.argmax(gaps))
        loss = (float(gaps[i]), f"step {i + 1}")
    gmed = float(np.median(list(ref["grad_norms"].values())))
    moving = [k for k, g in ref["grad_norms"].items() if g >= NOUGHT_GRAD * gmed]
    return {
        "loss_gap": loss,
        "grad_norm_gap": worst_leaf_gap(prog["grad_norms"], ref["grad_norms"]),
        "change_gap": worst_leaf_gap(prog["change_norms"], ref["change_norms"], moving),
    }


def judge(read: dict, limits: dict) -> tuple[bool, list]:
    """-> (correct, [{name, value, limit, at}]). A number with no limit, or
    one that is not finite, is not correct."""
    rows, ok = [], True
    for name, (value, at) in read.items():
        limit = limits.get(name)
        fine = limit is not None and math.isfinite(value) and value <= limit
        ok &= fine
        rows.append({"name": name, "value": value, "limit": limit, "at": at})
    return ok, rows
