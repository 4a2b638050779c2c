"""Faults planted under the timed path, to show that the comparison catches
them (tests/test_chipbench_faults.py at a test's size, calibrate.py on the
chip). Each is a context manager that patches the program for its duration.

- unchanged: the step returns its state unchanged.
- half_batch: the step sees only the first half of its rows (each twice),
  so its mean is taken over half the batch.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


def _first_half_twice(batch):
    half = jax.tree.leaves(batch)[0].shape[0] // 2
    return {k: jnp.concatenate([v[:half], v[:half]]) for k, v in batch.items()}


@contextlib.contextmanager
def unchanged():
    import repro.launch.train as train

    spmd_step = train.spmd_step

    def step(*a, **k):
        fn = spmd_step(*a, **k)
        return lambda state, batch: (state, fn(state, batch)[1])

    train.spmd_step = step
    try:
        yield
    finally:
        train.spmd_step = spmd_step


@contextlib.contextmanager
def half_batch():
    import repro.launch.train as train

    spmd_step = train.spmd_step

    def step(*a, **k):
        fn = spmd_step(*a, **k)
        return lambda state, batch: fn(state, _first_half_twice(batch))

    train.spmd_step = step
    try:
        yield
    finally:
        train.spmd_step = spmd_step


FAULTS = {"unchanged": unchanged, "half_batch": half_batch}
