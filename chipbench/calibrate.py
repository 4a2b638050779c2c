"""Readings from which the comparison's limits are set; not part of a run.

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--faults half_batch --fault-seeds 7,8,9]

For each seed it prints one JSON line with the three numbers compared
(correctness.readings):
- program: the cell's runner, set up and driven through the check's steps,
  against the float32 reference;
- control: the reference itself in the program's place, computed one
  precision step down (fp8 matmuls), against the float32 reference;
- a fault (faults.py) planted under the program's timed path.
All in one process, so that set-up's compiles are paid once.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def program_readings(cell, seed, devices):
    import importlib

    run = importlib.import_module(f"chipbench.runners.{cell.cfg['runner']}").Run(
        cell.cfg, cell.mix, seed, devices)
    run.setup(0)
    run.window(0)
    run.free()
    return run.check()[2]


def control_readings(cell, seed):
    from chipbench.runners import common

    n = cell.cfg["check"]["steps"]
    traffic = common.traffic_for(cell.cfg, cell.mix, seed)
    batches = [traffic.batch(i) for i in range(n)]
    prog = common.reference_as_program(cell.cfg, seed, batches, "fp8")
    return common.check(cell.cfg, seed, prog, batches)[2]


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    seeds = lambda s: [int(x) for x in s.split(",") if x]

    from chipbench import device as chip
    from chipbench import faults
    from chipbench.run import resolve

    cell = resolve(args.workload)
    devices = chip.chips(cell.chips)
    import jax

    from repro.launch.train import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    def emit(kind, seed, fn):
        t = time.perf_counter()
        try:
            read = {k: {"value": v, "at": at} for k, (v, at) in fn().items()}
        except Exception as e:  # noqa: BLE001 - a crash is a reading too
            read = {"error": repr(e)[:300]}
        print(json.dumps({"kind": kind, "seed": seed, "readings": read,
                          "seconds": time.perf_counter() - t}), flush=True)

    for s in seeds(args.seeds):
        emit("program", s, lambda: program_readings(cell, s, devices))
    for s in seeds(args.control_seeds):
        emit("control", s, lambda: control_readings(cell, s))
    for f in [x for x in args.faults.split(",") if x]:
        for s in seeds(args.fault_seeds):
            def planted():
                with faults.FAULTS[f]():
                    return program_readings(cell, s, devices)
            emit(f"fault:{f}", s, planted)
    return 0


if __name__ == "__main__":
    sys.exit(main())
