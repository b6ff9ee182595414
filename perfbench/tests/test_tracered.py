"""The reduction from a trace to device metrics, on plain lists: a small
hand-made trace for the arithmetic, and a recorded one (the first programs
of a traced `val4-flood` window on a v5e, cut down by `--trace-dump`) for
the names and shapes a real trace has."""

import json
import os

import pytest

from perfbench.harness import tracered as t

RECORDED = os.path.join(os.path.dirname(__file__), "recorded_trace.json")


def test_busy_union_merges_overlaps_and_keeps_gaps():
    events = [["a", 100, 50], ["b", 120, 10], ["c", 140, 30], ["d", 300, 20]]
    assert t.busy_union(events) == [(100, 170), (300, 320)]
    assert t.busy_seconds(events) == pytest.approx(90e-9)
    assert t.busy_union([]) == []


def test_idle_gaps_are_named_by_what_ended_them_longest_first():
    modules = [["jit_f(1)", 100, 50], ["jit_f(1)", 400, 50], ["jit_f(2)", 500, 50]]
    gaps = t.idle_gaps(modules, 0, 1000)
    assert gaps[0] == ["before_window_end", pytest.approx(450e-9)]
    assert gaps[1] == ["before_jit_f(1)", pytest.approx(250e-9)]
    assert [g[0] for g in gaps] == [
        "before_window_end", "before_jit_f(1)", "before_jit_f(1)", "before_jit_f(2)"
    ]
    assert len(t.idle_gaps(modules * 10, 0, 1000, top=3)) == 3


def test_ops_by_time_sums_by_name():
    ops = [["fusion", 0, 10], ["while", 10, 100], ["fusion", 110, 15]]
    assert t.ops_by_time(ops) == [["while", pytest.approx(100e-9)], ["fusion", pytest.approx(25e-9)]]


def test_op_name_is_the_head_of_the_hlo_line():
    assert t.op_name("%pad_add_fusion.8381 = s32[4096,63]{0,1} fusion(s32[] %x)") == "pad_add_fusion"
    assert t.op_name("%while.3 = (s32[]) while(%t), body=%b.1") == "while"
    assert t.op_name("%custom-call = s32[4096,32] custom-call()") == "custom-call"


def test_reduce_device_takes_whole_cycles_on_the_traces_own_clock():
    ms = 1_000_000
    modules = [["jit_f(7)", k * 100 * ms, 20 * ms] for k in range(5)]
    # operations read for the first two programs only, idle a tenth of each
    ops = []
    for k in range(2):
        ops += [["while", k * 100 * ms, 12 * ms], ["fusion", k * 100 * ms + 14 * ms, 6 * ms]]
    got = t.reduce_device({"modules": modules, "ops": ops})
    assert got["ops_share_of_module"] == pytest.approx(0.9)
    # four cycles of 100 ms, first start to last start: four programs, four gaps
    assert got["window_s"] == pytest.approx(0.400)
    assert got["busy_s"] == pytest.approx(0.9 * 4 * 0.020)
    assert got["steps"] == [pytest.approx(0.020)] * 5
    assert dict(got["device_ops"])["while"] == pytest.approx(5 * 0.012)
    assert got["idle_gaps"] == [["before_jit_f(7)", pytest.approx(0.080)]] * 4
    # one program is no cycle: nothing to read
    assert t.reduce_device({"modules": modules[:1], "ops": []})["window_s"] == 0.0
    assert t.reduce_device({"modules": [], "ops": []})["busy_s"] == 0.0


def test_operations_laid_to_the_wrong_program_are_an_error_not_a_cap():
    ms = 1_000_000
    modules = [["jit_f(7)", 0, 10 * ms], ["jit_f(7)", 100 * ms, 10 * ms]]
    ops = [["while", 0, 9 * ms], ["fusion", 9 * ms, 90 * ms], ["copy", 100 * ms, 10 * ms]]
    with pytest.raises(ValueError, match="wrong program"):
        t.reduce_device({"modules": modules, "ops": ops})


def test_the_trace_starts_so_many_steps_before_the_close():
    from perfbench.harness.drive import Tracing

    tr = Tracing(True, "/nonexistent", 5, lambda: 0)
    # 60 steps in 21 s: five steps are 1.75 s, and start_trace's own time
    assert tr.lookback(21.0, 60) == pytest.approx(1.75 + Tracing.START_S)
    assert tr.lookback(3.0, 0) == Tracing.MAX_S  # nothing dispatched yet
    assert tr.lookback(30.0, 10) == Tracing.MAX_S  # never more than this
    assert Tracing(False, "/nonexistent", 5, lambda: 0).reduce() is None


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace beside this test")
def test_the_recorded_trace_reduces_to_what_was_read_on_the_chip():
    loaded = json.load(open(RECORDED))
    assert "/device:TPU:0" in loaded["devices"]
    assert set(loaded["lines"]["/device:TPU:0"]) >= {t.LINE_MODULES, t.LINE_OPS}
    dev = loaded["devices"]["/device:TPU:0"]
    assert all(name.startswith("jit_f(") for name, _, _ in dev["modules"])
    got = t.reduce_device({"modules": dev["modules"], "ops": []})
    # the (4096, 4096) step program on a v5e: 27.4 ms a dispatch
    assert len(got["steps"]) == len(dev["modules"]) >= 2
    assert all(0.020 < s < 0.040 for s in got["steps"])
    assert got["busy_s"] == pytest.approx(sum(got["steps"][:-1]))
    starts = sorted(m[1] for m in dev["modules"])
    assert got["window_s"] == pytest.approx((starts[-1] - starts[0]) / 1e9)
    assert 0.80 < 1 - got["busy_s"] / got["window_s"] < 0.95  # a flood cycle: 27 ms of 230
    assert all(name.startswith("before_jit_f(") for name, _ in got["idle_gaps"])
    # the operations kept are the head of the first program
    first = dev["modules"][0]
    assert all(first[1] <= s and s + d <= first[1] + first[2] for _, s, d in dev["ops"])
    assert 0 < t.busy_seconds(dev["ops"]) <= first[2] / 1e9
