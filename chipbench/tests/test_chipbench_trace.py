"""The reduction from a profiler trace to busy/idle time, time under a scope
and idle gaps by host span: on a hand-made record with known answers, and
on a small trace recorded on a TPU v5e chip (tests/data)."""
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
    r = trace.reduce(_record())
    assert r["scope_s"]["attn_core"] == pytest.approx(0.020 + 0.005)
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


# A compiled step's text, cut down: an outer loop (the microbatches) holds
# one attention op of its own and two loops with none inside them; one of
# those holds attn_core ops in a fusion and a residual named outside it.
HLO = """HloModule jit_train_step, entry_computation_layout={()->()}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %exponential.1 = f32[8]{0} exponential(%param_0), metadata={op_name="jit(step)/while/body/attn_core/exp"}
}

%cond.1 (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  ROOT %lt.1 = pred[] compare(%gte.0, %c.0), direction=LT, metadata={op_name="jit(step)/while/cond/lt"}
}

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %fusion.1 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/attn_core/exp"}
  %dynamic-update-slice.1 = f32[4,8]{1,0} dynamic-update-slice(%a, %fusion.1, %i, %z), metadata={op_name="jit(step)/while/body/dynamic_update_slice"}
  %copy.1 = f32[8]{0} copy(%fusion.1)
  ROOT %tuple.1 = (s32[], f32[8]) tuple(%add.1, %copy.1)
}

%cond.2 (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  ROOT %lt.2 = pred[] compare(%gte.0, %c.0), direction=LT, metadata={op_name="jit(step)/while/cond/lt"}
}

%body.2 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  ROOT %fusion.2 = f32[8]{0} fusion(%gte.1), kind=kLoop, metadata={op_name="jit(step)/while/body/mlp/dot_general"}
}

%cond.3 (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  ROOT %lt.3 = pred[] compare(%gte.0, %c.0), direction=LT, metadata={op_name="jit(step)/while/cond/lt"}
}

%body.3 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(step)/while/body/attn_core/ne"}
  %fusion.4 = f32[8]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(step)/while/body/mlp/dot_general"}
  %while.1 = (s32[], f32[8]) while(%t), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/while/body/while"}
  ROOT %while.2 = (s32[], f32[8]) while(%t), condition=%cond.2, body=%body.2, metadata={op_name="jit(step)/while/body/while"}
}

ENTRY %main.1 (a: f32[8]) -> (s32[], f32[8]) {
  %a = f32[8]{0} parameter(0)
  ROOT %while.3 = (s32[], f32[8]) while(%t), condition=%cond.3, body=%body.3, metadata={op_name="jit(step)/while"}
}
"""


def test_an_innermost_loop_holding_a_scope_belongs_to_it_whole():
    (module, names), = trace.op_names(HLO).items()
    assert module == "jit_train_step"
    # the attention loop: its residual, its plumbing and its condition too
    for instr in ("dynamic-update-slice.1", "copy.1", "tuple.1", "lt.1"):
        assert "[attn_core loop]" in names[instr], instr
    assert names["fusion.1"] == "jit(step)/while/body/attn_core/exp"
    # the other innermost loop holds no attention; the outer loop is not
    # innermost, so only its own attention op counts
    assert names["fusion.2"] == "jit(step)/while/body/mlp/dot_general"
    assert names["fusion.4"] == "jit(step)/while/body/mlp/dot_general"
    assert names["fusion.3"] == "jit(step)/while/body/attn_core/ne"
    assert "lt.2" in names and "attn_core" not in names["lt.2"]
    assert "attn_core" not in names["lt.3"]


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
    """Three steps of the qwen3-8b.packed cell's window, traced on one TPU v5
    lite chip, with scopes from the compiled step's text (op_names): the
    chunked attention's scan counts whole, its residuals too."""
    rec = trace.read(RECORDED)
    r = trace.reduce(rec)
    (lo, hi), = [(s, s + d) for n, s, d in rec["host"] if n == "window"]
    (plane, ops), = rec["devices"].items()
    assert r["busy_s_per_chip"][plane] == pytest.approx(_sweep_busy(ops, lo, hi) / 1e9, abs=1e-9)
    attn = sum(min(s + d, hi) - max(s, lo) for _, s, d, sc in ops
               if "attn_core" in sc and min(s + d, hi) > max(s, lo))
    assert attn > 0 and r["scope_s"]["attn_core"] == pytest.approx(attn / 1e9, abs=1e-9)
    assert 0 < r["scope_s"]["attn_core"] < r["busy_s"] < r["window_s"]
    # the scan's residual slices hold much of attention's time
    loop = sum(min(s + d, hi) - max(s, lo) for _, s, d, sc in ops
               if "[attn_core loop]" in sc and "dynamic" in sc and min(s + d, hi) > max(s, lo))
    assert 0.4 < loop / attn < 0.7
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"], abs=1e-6)
    # the device waits on the host between steps, while it blocks on the
    # finished step and moves the next batch
    assert max(idle, key=idle.get) in ("block", "batch_to_device", "train_step")
    assert all(" " in name or name.split(".")[0] for name, _ in r["device_ops"])
