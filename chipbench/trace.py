"""From a profiler trace to the numbers the per-layer metrics read.

`load` reads the `.xplane.pb` that `jax.profiler` writes into a small
record: for each TPU chip, its operations as [name, start_ns, duration_ns,
scope]; and the benchmark's own host spans as [name, start_ns,
duration_ns]. Host and device times share one clock in the trace. A TPU
trace names each operation by its HLO instruction and carries no name
stack, so the scope (where `jax.named_scope` names appear) comes from the
`op_name` metadata of the compiled programs' text (`op_names`), matched by
module and instruction. An operation in an innermost loop (a while loop
with no loop inside it) that holds operations of a scope belongs to that
scope too: JAX names the residuals a scan writes and reads (its
dynamic_update_slice and dynamic_slice) outside the scope the scan was
built in, though they are the scan's work. Control-flow containers (while,
conditional, call) span the operations of their bodies and are left out.

`reduce` takes the window from the host span named 'window' and gives:
- busy_s per chip: the union of the chip's operation intervals inside the
  window (overlapping operations count once), and window_s;
- scope_s: device seconds of operations whose scope holds a given name;
- device_ops: the operations that took most device time, by name;
- idle_gaps: the window's idle device time, each gap given to the innermost
  benchmark span that covers most of it ('none' where no span does).
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
SCOPES = ("attn_core",)
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
CONTAINERS = ("while", "conditional", "call")
_INSTR = re.compile(r"%([^ ]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^ ]+) ")
_DEF = re.compile(r"^\s*(?:ROOT )?%([^ ]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%([^\s,)}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_WHILE = re.compile(r"[)\]}] while\(.*condition=%([^\s,]+), body=%([^\s,]+)")


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


def _computations(hlo_text):
    """{computation: [(instruction, op_name, computations it calls,
    (condition, body) for a while or None)]}, and the module's name."""
    module = hlo_text.split(None, 2)[1].rstrip(",")
    comps, current = {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and line.rstrip().endswith("{"):
            current = comps.setdefault(m.group(1), [])
            continue
        m = _DEF.match(line)
        if m and current is not None:
            meta = _OP_NAME.search(line)
            called = _CALLED.findall(line)
            for group in _BRANCHES.findall(line):
                called += [c.strip().lstrip("%") for c in group.split(",")]
            loop = _WHILE.search(line)
            current.append((m.group(1), meta.group(1) if meta else "", called,
                            loop.groups() if loop else None))
    return module, comps


def op_names(hlo_text, scopes=SCOPES) -> dict:
    """{module: {instruction: scope}} from a compiled program's text: each
    instruction's op_name, and for the operations of an innermost loop that
    holds operations of a scope, '<op_name> [<scope> loop]'."""
    module, comps = _computations(hlo_text)
    names = {i: op for body in comps.values() for i, op, _, _ in body if op}
    reach = {}

    def reachable(c):
        if c not in reach:
            reach[c] = {c}
            for _, _, called, _ in comps.get(c, []):
                for d in called:
                    reach[c] |= reachable(d)
        return reach[c]

    for body in list(comps.values()):
        for _, _, _, loop in body:
            if loop is None:
                continue
            inside = reachable(loop[0]) | reachable(loop[1])
            ops = [x for c in inside for x in comps.get(c, [])]
            if any(x[3] for x in ops):
                continue  # not innermost
            for sc in scopes:
                if any(sc in x[1] for x in ops):
                    for c in loop:
                        for instr, op, _, _ in comps.get(c, []):
                            if sc not in op:
                                names[instr] = f"{op} [{sc} loop]"
    return {module: names}


def instruction(event_name):
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12'."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0]


def _is_container(instr):
    return instr.rsplit(".", 1)[0] in CONTAINERS


def load(path, names=None) -> dict:
    """Read an .xplane.pb into {'devices': {plane: [[instr, start, dur, scope]]},
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


def _label(instr, scope):
    """An operation's name for the breakdown: its instruction and the end of
    its name stack."""
    tail = "/".join(scope.split("/")[-3:])
    return f"{instr} {tail}".strip()


def reduce(record, scopes=SCOPES, top=10) -> dict:
    windows = [(s, s + d) for n, s, d in record["host"] if n == "window"]
    if not windows:
        raise ValueError("the trace holds no 'window' span")
    lo, hi = windows[0]
    spans = [(n, s, s + d) for n, s, d in record["host"] if n != "window" and s < hi and s + d > lo]
    busy_s, scope_s, op_s, idle = {}, defaultdict(float), defaultdict(float), defaultdict(float)
    for plane, ops in sorted(record["devices"].items()):
        inside = []
        for name, s, d, scope in ops:
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            inside.append((a, b))
            op_s[_label(name, scope)] += (b - a) / 1e9
            for sc in scopes:
                if sc in scope:
                    scope_s[sc] += (b - a) / 1e9
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
