"""Run one benchmark cell once on the TPU chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from BENCHMARK.json: the cell's configuration
(chipbench/configs/<config>.json), its traffic mix
(chipbench/traffic/<mix>.json), the runner the configuration names
(chipbench/runners/<runner>.py), the arch module and the plain reference of
its model type (chipbench/archs/<model_type>.py, whose `work` counts a step's
work, and chipbench/reference/<model_type>.py), and a reader per metric
(chipbench/end_to_end/<metric>.py, chipbench/layer_metrics/<metric>.py).

A run loads, warms up (set-up), measures for --seconds, then checks what the
timed path produced against the plain reference and prints, as its last
line of standard output, one JSON object: correct, attempted, failed,
metrics, device, with --trace 1 a breakdown, and last the numbers compared
with their limits. With --trace 0 the metrics are the cell's end-to-end
ones, with --trace 1 its per-layer ones, from a profiler trace of the
window. With no TPU, or fewer chips than the cell needs, it exits 2 and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def load_benchmark(root=ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _applies(metric, workload):
    return "workloads" not in metric or workload["name"] in metric["workloads"]


def resolve(name, root=ROOT) -> SimpleNamespace:
    """The cell `name` with its configuration, mix, runner and metrics, all
    found by name under `root`."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = json.loads((Path(root) / conf["file"]).read_text())
    cfg["name"] = conf["name"]
    base = Path(root) / "chipbench"
    mix = json.loads((base / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, w)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (_applies(m, w) if "workloads" in m else m["moves"] in reported)]
    for kind, metrics in (("end_to_end", e2e), ("layer_metrics", layer)):
        for m in metrics:
            if not (base / kind / f"{m['name']}.py").is_file():
                raise FileNotFoundError(f"no reader {kind}/{m['name']}.py")
    if not (base / "runners" / f"{cfg['runner']}.py").is_file():
        raise FileNotFoundError(f"no runner runners/{cfg['runner']}.py")
    arch = importlib.import_module(f"chipbench.archs.{cfg['model_type']}")
    if not callable(getattr(arch, "work", None)):
        raise TypeError(f"archs/{cfg['model_type']}.py has no work(cfg, segment_ids)")
    return SimpleNamespace(workload=w, cfg=cfg, mix=mix, chips=w["chips"],
                           end_to_end=e2e, per_layer=layer)


def _read(kind, metrics, ctx):
    out = {}
    for m in metrics:
        value = importlib.import_module(f"chipbench.{kind}.{m['name']}").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def measure(cell, seed, seconds, trace, devices, *, t_start=None):
    """Set up, measure, check; -> (the result object, the numbers compared
    as rows of name, value, limit and where the worst reading was)."""
    import jax

    from chipbench import device as chip
    from chipbench import trace as tr

    t_start = time.perf_counter() if t_start is None else t_start
    runner = importlib.import_module(f"chipbench.runners.{cell.cfg['runner']}")
    run = runner.Run(cell.cfg, cell.mix, seed, devices)
    run.setup(seconds)
    setup_s = time.perf_counter() - t_start

    record = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tmp, profiler_options=tr.options())
    try:
        steps = run.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    if trace:
        names = {}
        for text in run.program_texts():
            names.update(tr.op_names(text))
        record = tr.load(tr.find_xplane(tmp), names)
        shutil.rmtree(tmp, ignore_errors=True)
    stats = [d.memory_stats() or {} for d in devices]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    run.free()
    correct, rows, _ = run.check()

    reduced = tr.reduce(record) if record is not None else None
    ctx = SimpleNamespace(cfg=cell.cfg, chips=cell.chips, steps=steps,
                          trace=reduced, setup_s=setup_s, peak_bytes=peak,
                          peaks=_peaks(devices))
    metrics = (_read("layer_metrics", cell.per_layer, ctx) if trace
               else _read("end_to_end", cell.end_to_end, ctx))
    dev = chip.describe(devices)
    dev["memory_peak_bytes"] = peak
    result = {"correct": bool(correct), "attempted": len(steps),
              "failed": sum(1 for s in steps if not math.isfinite(s["loss"])),
              "metrics": metrics,
              "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]} for r in rows}
    return result, rows


def _peaks(devices):
    from chipbench import device as chip

    if devices[0].platform != "tpu":  # a CPU run (tests) has no peaks
        return {"bf16_flops_per_s": math.nan, "hbm_bytes_per_s": math.nan}
    return chip.peaks(devices[0].device_kind)


def report(result, rows, out=sys.stdout, err=sys.stderr):
    """The numbers compared, as the last lines of standard error, and the
    result as the last line of standard output."""
    for r in rows:
        print(f"check {r['name']} {r['value']!r} limit {r['limit']!r} (worst at {r['at']})",
              file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = resolve(args.workload)

    from chipbench import device as chip

    try:
        devices = chip.chips(cell.chips)
        chip.peaks(devices[0].device_kind)
    except (chip.NoChip, KeyError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2

    import jax

    from repro.launch.train import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    report(*measure(cell, args.seed, args.seconds, args.trace, devices, t_start=T_START))
    return 0


if __name__ == "__main__":
    sys.exit(main())
