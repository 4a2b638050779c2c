"""The reduction from a profiler trace to busy/idle time, time by innermost
scope and idle gaps by host span: on a hand-made record with known answers,
on a cut-down compiled program's text, and on a small trace recorded on a
TPU v5e chip (tests/data)."""
import pytest

from chipbench import trace
from chipbench.tests import tiny

MS = 1_000_000  # ns


def _record():
    # window 0..100 ms on two chips; host spans inside it
    return {
        "devices": {
            "/device:TPU:0": [
                ["fusion.1", 10 * MS, 20 * MS, "jit(step)/attn_core/dot_general"],
                ["fusion.2", 20 * MS, 20 * MS, "jit(step)/mlp/dot_general"],  # overlaps .1
                ["copy.3", 60 * MS, 10 * MS, ""],
                ["fusion.4", -5 * MS, 10 * MS, "jit(step)/attn_core/exp"],  # starts before
                ["fusion.5", 95 * MS, 10 * MS, "jit(step)/mlp/add"],  # ends after
            ],
            "/device:TPU:1": [["fusion.9", 0, 50 * MS, "x"]],
        },
        "host": [
            ["window", 0, 100 * MS],
            ["train_step", 0, 45 * MS],
            ["block", 45 * MS, 30 * MS],
            ["batch_to_device", 75 * MS, 25 * MS],
            ["train_step", 500 * MS, 10 * MS],  # outside the window
        ],
    }


def test_busy_is_the_union_of_overlapping_operations_inside_the_window():
    r = trace.reduce(_record())
    assert r["window_s"] == pytest.approx(0.1)
    # chip 0: [0,5) + [10,40) + [60,70) + [95,100) = 50 ms; chip 1: 50 ms
    assert r["busy_s_per_chip"]["/device:TPU:0"] == pytest.approx(0.050)
    assert r["busy_s_per_chip"]["/device:TPU:1"] == pytest.approx(0.050)
    assert r["busy_s"] == pytest.approx(0.050)


def test_time_under_a_scope_counts_each_operation_inside_the_window():
    r = trace.reduce(_record(), scopes=("attn_core", "mlp"))
    assert r["scope_s"]["attn_core"] == pytest.approx(0.020 + 0.005)
    assert r["scope_s"]["mlp"] == pytest.approx(0.020 + 0.005)
    assert r["scope_s"]["unscoped"] == pytest.approx(0.010 + 0.050)  # '' and 'x'
    assert set(r["scope_s"]) == {"attn_core", "mlp", "unscoped"}
    ops = dict(r["device_ops"])
    assert ops["fusion.2 jit(step)/mlp/dot_general"] == pytest.approx(0.020)
    assert r["device_ops"][0] == ["fusion.9 x", pytest.approx(0.050)]


def test_idle_gaps_go_to_the_host_span_that_covers_most_of_them():
    r = trace.reduce(_record())
    idle = dict(r["idle_gaps"])
    # chip 0 gaps: [5,10) train_step, [40,60) block (45..60) over train_step
    # (40..45), [70,95) batch_to_device (75..95) over block (70..75);
    # chip 1 gap [50,100): block covers 25 ms, batch_to_device 25 ms: the
    # shorter span wins the tie
    assert idle["train_step"] == pytest.approx(0.005)
    assert idle["block"] == pytest.approx(0.020)
    assert idle["batch_to_device"] == pytest.approx(0.025 + 0.050)
    assert sum(idle.values()) == pytest.approx(2 * 0.1 - 2 * 0.05)


# A compiled step's text, cut down: the microbatch loop's body holds the
# attention core nested in the projections, a backward matmul under a
# jvp/transpose wrap, a parameter path that names a layer but is no scope,
# a scan residual and a copy named by no scope.
HLO = """HloModule jit_train_step, entry_computation_layout={()->()}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %exponential.1 = f32[8]{0} exponential(%param_0), metadata={op_name="jit(train_step)/while/body/attn_proj/attn_core/exp"}
}

%cond.1 (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  ROOT %lt.1 = pred[] compare(%gte.0, %c.0), direction=LT, metadata={op_name="jit(train_step)/while/cond/lt"}
}

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %fusion.1 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/while/body/attn_proj/attn_core/exp"}
  %fusion.2 = f32[8]{0} fusion(%gte.1), kind=kOutput, metadata={op_name="jit(train_step)/while/body/transpose(jvp(mlp))/bsd,df->bsf/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%gte.1), kind=kLoop, metadata={op_name="jit(train_step)/while/body/transpose(jvp(attn_proj))/attn_core/dot_general"}
  %convert.1 = f32[8]{0} convert(%gte.1), metadata={op_name="jit(train_step)/state['params']['layers'][0]['mlp']/convert_element_type"}
  %fusion.4 = f32[8]{0} fusion(%gte.1), kind=kLoop, metadata={op_name="jit(train_step)/while/body/mlp_gate/add"}
  %dynamic-update-slice.1 = f32[4,8]{1,0} dynamic-update-slice(%a, %fusion.1, %i, %z), metadata={op_name="jit(train_step)/while/body/dynamic_update_slice"}
  %copy.1 = f32[8]{0} copy(%fusion.1)
  ROOT %tuple.1 = (s32[], f32[8]) tuple(%add.1, %copy.1)
}

ENTRY %main.1 (a: f32[8]) -> (s32[], f32[8]) {
  %a = f32[8]{0} parameter(0)
  ROOT %while.1 = (s32[], f32[8]) while(%t), condition=%cond.1, body=%body.1, metadata={op_name="jit(train_step)/while"}
}
"""
SCOPES = ("attn_core", "attn_proj", "mlp", "lm_head", "optimizer")


@pytest.mark.parametrize("instr,scope", [
    ("fusion.1", "attn_core"),  # nested in attn_proj: the innermost
    ("fusion.2", "mlp"),  # inside transpose(jvp(...))
    ("fusion.3", "attn_core"),  # nested in a wrapped scope
    ("convert.1", None),  # a parameter path names no scope
    ("fusion.4", None),  # a component that only starts with a scope's name
    ("dynamic-update-slice.1", None),  # a scan's residual stays outside attention
    ("lt.1", None),
])
def test_op_names_give_each_instruction_its_innermost_scope(instr, scope):
    (module, names), = trace.op_names(HLO).items()
    assert module == "jit_train_step"
    assert trace.scope_of(names[instr], SCOPES) == scope
    assert "copy.1" not in names and "tuple.1" not in names  # no metadata


def test_reduce_splits_a_compiled_programs_operations_by_innermost_scope():
    names = trace.op_names(HLO)["jit_train_step"]
    instrs = ["fusion.1", "fusion.2", "fusion.3", "convert.1", "fusion.4",
              "dynamic-update-slice.1", "copy.1"]
    ops = [[i, k * MS, MS, names.get(i, "")] for k, i in enumerate(instrs)]
    rec = {"devices": {"/device:TPU:0": ops}, "host": [["window", 0, 100 * MS]]}
    r = trace.reduce(rec, scopes=SCOPES)
    assert r["scope_s"] == pytest.approx({"attn_core": 0.002, "mlp": 0.001, "unscoped": 0.004})
    assert sum(r["scope_s"].values()) == pytest.approx(r["busy_s"])


def test_reduce_reads_the_programs_scope_names_when_it_runs(monkeypatch):
    from repro import tracing

    rec = {"devices": {"/device:TPU:0": [["fusion.1", 0, MS, "jit(step)/mlp/moe/dot_general"],
                                          ["fusion.2", MS, MS, "jit(step)/attn_core/exp"]]},
           "host": [["window", 0, 10 * MS]]}
    assert trace.reduce(rec)["scope_s"] == pytest.approx({"mlp": 0.001, "attn_core": 0.001})
    monkeypatch.setattr(tracing, "SCOPES", tracing.SCOPES + ("moe",))
    assert trace.reduce(rec)["scope_s"] == pytest.approx({"moe": 0.001, "attn_core": 0.001})


def test_a_trace_without_a_window_or_a_chip_is_refused():
    rec = _record()
    with pytest.raises(ValueError, match="window"):
        trace.reduce({"devices": rec["devices"], "host": rec["host"][1:]})
    with pytest.raises(ValueError, match="no TPU chip"):
        trace.reduce({"devices": {}, "host": rec["host"]})


RECORDED = tiny.DATA / "trace_packed_v5e.json.gz"


def _sweep_busy(ops, lo, hi):
    """Busy nanoseconds by a sweep over +1/-1 edges: a second way to the union."""
    edges = []
    for _, s, d, _ in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    busy, depth, last = 0, 0, None
    for t, step in sorted(edges):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_a_recorded_v5e_trace_reduces_to_its_union_scope_time_and_gaps():
    """Three steps of the qwen3-8b.packed cell's window (chunked attention
    then), traced on one TPU v5 lite chip, with each operation's op_name from
    the compiled step's text (op_names): the scopes partition operation time,
    none counted twice."""
    rec = trace.read(RECORDED)
    r = trace.reduce(rec, scopes=SCOPES)
    (lo, hi), = [(s, s + d) for n, s, d in rec["host"] if n == "window"]
    (plane, ops), = rec["devices"].items()
    assert r["busy_s_per_chip"][plane] == pytest.approx(_sweep_busy(ops, lo, hi) / 1e9, abs=1e-9)
    clipped = [(min(s + d, hi) - max(s, lo), op) for _, s, d, op in ops if min(s + d, hi) > max(s, lo)]
    attn = sum(t for t, op in clipped if trace.scope_of(op, SCOPES) == "attn_core")
    assert attn > 0 and r["scope_s"]["attn_core"] == pytest.approx(attn / 1e9, abs=1e-9)
    assert 0 < r["scope_s"]["attn_core"] < r["busy_s"] < r["window_s"]
    # a partition: every operation in exactly one scope or 'unscoped'
    assert sum(r["scope_s"].values()) == pytest.approx(sum(t for t, _ in clipped) / 1e9, abs=1e-9)
    assert set(r["scope_s"]) <= set(SCOPES) | {"unscoped"}
    # the chunked scan's residual slices are named outside attn_core
    residual = sum(t for t, op in clipped if "dynamic" in op and "rematted" in op)
    assert residual > 0 and r["scope_s"]["unscoped"] * 1e9 > residual
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"], abs=1e-6)
    # the device waits on the host between steps, while it blocks on the
    # finished step and moves the next batch
    assert max(idle, key=idle.get) in ("block", "batch_to_device", "train_step")
    assert all(" " in name or name.split(".")[0] for name, _ in r["device_ops"])
