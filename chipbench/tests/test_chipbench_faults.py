"""The comparison that decides `correct`, shown to fail: a whole run of the
SPMD runner with the chip look skipped, at a test's size on the CPU, once sound,
once with each fault the cell can have planted under the timed path, and
the control (the reference one precision step down in the program's place).

The limits of these tiny configurations were set, as the benchmark's are,
between the readings of sound runs and those of the control and the faults
at this size (PERF.md); the benchmark's own runs never run the control."""
import jax
import pytest

from chipbench import calibrate, correctness, faults, run
from chipbench.tests import tiny

SEED = 2**31 + 11


def _run():
    res, rows = run.measure(tiny.cell(), SEED, 0.2, 0, jax.devices())
    return res, {r["name"]: r["value"] for r in rows}


def test_a_sound_run_is_correct():
    res, checks = _run()
    assert res["correct"], checks
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_run_with_a_planted_fault_is_not_correct(fault):
    with faults.FAULTS[fault]():
        res, checks = _run()
    assert not res["correct"], checks


def test_the_control_is_not_correct():
    cell = tiny.cell()
    read = calibrate.control_readings(cell, SEED)
    ok, rows = correctness.judge(read, cell.cfg["check"]["limits"])
    assert not ok, rows
