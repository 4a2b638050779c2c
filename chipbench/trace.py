"""From a profiler trace to the numbers the per-layer metrics read.

`load` reads the `.xplane.pb` that `jax.profiler` writes into a small
record: for each TPU chip, its operations as [name, start_ns, duration_ns,
op_name]; and the benchmark's own host spans as [name, start_ns,
duration_ns]. Host and device times share one clock in the trace. A TPU
trace names each operation by its HLO instruction and carries no name
stack, so the name stack comes from the `op_name` metadata of the compiled
programs' text (`op_names`), matched by module and instruction.
Control-flow containers (while, conditional, call) span the operations of
their bodies and are left out.

`reduce` takes the window from the host span named 'window' and gives:
- busy_s per chip: the union of the chip's operation intervals inside the
  window (overlapping operations count once), and window_s;
- scope_s: device seconds of the operations inside the window by the
  innermost program scope in their name stack (`scope_of`), summed over the
  chips; an operation under no scope counts under 'unscoped', so each
  operation counts once;
- device_ops: the operations that took most device time, by name;
- idle_gaps: the window's idle device time, each gap given to the innermost
  benchmark span that covers most of it ('none' where no span does).

The scope names are the program's own (`repro.tracing.SCOPES`), read when a
trace is reduced, so a scope the program adds is read with no edit here; how
an operation is matched to a scope is this module's.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from collections import defaultdict

HOST_SPANS = ("window", "batch_to_device", "train_step", "block")
UNSCOPED = "unscoped"
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
CONTAINERS = ("while", "conditional", "call")
_INSTR = re.compile(r"%([^ ]+) = ")
_DEF = re.compile(r"^\s*(?:ROOT )?%([^ ]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")


def options():
    """Profiler options: device and host tracing, no Python call tracing."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def find_xplane(directory):
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def scope_of(op_name, scopes):
    """The innermost of `scopes` in an operation's name stack, or None.

    A scope counts only as a whole component of the stack, also when JAX
    wraps it as `jvp(mlp)` or `transpose(jvp(mlp))`; a parameter path such as
    `state['params']['layers'][0]['ffn']` names no scope."""
    for part in reversed(op_name.split("/")):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        if part in scopes:
            return part
    return None


def op_names(hlo_text) -> dict:
    """{module: {instruction: op_name}} from a compiled program's text."""
    module = hlo_text.split(None, 2)[1].rstrip(",")
    names = {}
    for line in hlo_text.splitlines():
        d, meta = _DEF.match(line), _OP_NAME.search(line)
        if d and meta:
            names[d.group(1)] = meta.group(1)
    return {module: names}


def instruction(event_name):
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12'."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0]


def _is_container(instr):
    return instr.rsplit(".", 1)[0] in CONTAINERS


def load(path, names=None) -> dict:
    """Read an .xplane.pb into {'devices': {plane: [[instr, start, dur, op_name]]},
    'host': [[name, start, dur]]}; `names` as op_names gives."""
    from jax.profiler import ProfileData

    names = names or {}
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[len("/device:TPU:"):].isdigit():
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                           e.name.split("(")[0]) for e in lines.get(MODULE_LINE, []))
            starts = [m[0] for m in mods]
            ops = []
            for e in lines.get(OP_LINE, []):
                instr = instruction(e.name)
                if _is_container(instr):
                    continue
                i = bisect.bisect_right(starts, int(e.start_ns)) - 1
                module = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] else ""
                ops.append([instr, int(e.start_ns), int(e.duration_ns),
                            names.get(module, {}).get(instr, "")])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append([e.name, int(e.start_ns), int(e.duration_ns)])
    return {"devices": devices, "host": host}


def read(path) -> dict:
    """A record saved as gzipped JSON (the tests' recorded trace)."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _gaps(busy, lo, hi):
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def _owner(gap, spans):
    """The span that covers most of the gap; the shortest on a tie."""
    best, best_key = "none", (0, 0)
    for name, s, e in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover <= 0:
            continue
        key = (cover, -(e - s))
        if key > best_key:
            best, best_key = name, key
    return best


def _label(instr, op_name):
    """An operation's name for the breakdown: its instruction and the end of
    its name stack."""
    tail = "/".join(op_name.split("/")[-3:])
    return f"{instr} {tail}".strip()


def reduce(record, scopes=None, top=10) -> dict:
    """The window's numbers (module docstring); `scopes` defaults to the
    program's, as they are when this runs."""
    if scopes is None:
        from repro import tracing

        scopes = tracing.SCOPES
    windows = [(s, s + d) for n, s, d in record["host"] if n == "window"]
    if not windows:
        raise ValueError("the trace holds no 'window' span")
    lo, hi = windows[0]
    spans = [(n, s, s + d) for n, s, d in record["host"] if n != "window" and s < hi and s + d > lo]
    busy_s, scope_s, op_s, idle = {}, defaultdict(float), defaultdict(float), defaultdict(float)
    for plane, ops in sorted(record["devices"].items()):
        inside = []
        for name, s, d, op_name in ops:
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            inside.append((a, b))
            op_s[_label(name, op_name)] += (b - a) / 1e9
            scope_s[scope_of(op_name, scopes) or UNSCOPED] += (b - a) / 1e9
        busy = _union(inside)
        busy_s[plane] = sum(e - s for s, e in busy) / 1e9
        for gap in _gaps(busy, lo, hi):
            idle[_owner(gap, spans)] += (gap[1] - gap[0]) / 1e9
    if not busy_s:
        raise ValueError("the trace holds no TPU chip")
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s.values()) / len(busy_s),
        "busy_s_per_chip": busy_s,
        "scope_s": dict(scope_s),
        "device_ops": rank(op_s),
        "idle_gaps": rank(idle),
    }
