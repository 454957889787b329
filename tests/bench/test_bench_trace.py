"""Trace reduction: busy and idle time, program and kernel time, and the
roofline share, on a hand-made trace and on a small recorded one."""
import pathlib
from types import SimpleNamespace

import pytest

import benchpath  # noqa: F401
from harness import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000                      # nanoseconds
DECODE, PREFILL = 7, 9              # program ids


def hand_trace():
    spans = [("bench.window_start", 0, 1), ("bench.step_fn", 5 * MS, 40 * MS),
             ("bench.submit", 41 * MS, 44 * MS),
             ("bench.window_end", 100 * MS, 100 * MS + 1)]
    E = tr.Event
    ops = [E("fusion.1", 10 * MS, 20 * MS, DECODE),
           E("branch_0_fun.7", 15 * MS, 25 * MS, DECODE,
             "jit(<unknown>)/while/body/pallas_call"),
           E("fusion.2", 30 * MS, 35 * MS, DECODE),
           E("fusion.3", 60 * MS, 70 * MS, PREFILL),
           E("late", 150 * MS, 160 * MS, PREFILL)]
    modules = [E("jit__unknown(7)", 10 * MS, 35 * MS, DECODE),
               E("jit__unknown(9)", 60 * MS, 70 * MS, PREFILL),
               E("jit__unknown(9)", 90 * MS, 95 * MS, PREFILL)]
    return tr.Trace(spans, {"/device:TPU:0": {tr.OPS_LINE: ops,
                                              tr.MODULES_LINE: modules}})


def test_busy_is_the_union_of_ops_inside_the_window():
    t = hand_trace()
    assert t.window_s == pytest.approx(0.1)
    # [10,25] + [30,35] + [60,70] = 30 ms; the op at 150 ms is outside
    assert t.busy_s() == pytest.approx(0.030)


def test_kernel_ops_and_programs_are_found_by_their_source_op():
    t = hand_trace()
    assert t.op_seconds()["branch_0_fun.7"] == pytest.approx(0.010)
    assert t.matching_ops("nothing", "pallas_call") == \
        t.matching_ops("pallas_call")
    assert "late" not in t.op_seconds()
    assert [e.name for e in t.matching_ops("pallas_call")] == \
        ["branch_0_fun.7"]
    assert t.programs_running("pallas_call") == {DECODE}


def test_program_runs_split_by_kernel_and_by_admitting_steps():
    t = hand_trace()
    # host clock reads 2.0 s at the window's start span (trace time 0);
    # the step admitting requests ran from 2.055 s to 2.075 s
    steps = [SimpleNamespace(t_call=2.005, t_return=2.040, prefill_rows=[]),
             SimpleNamespace(t_call=2.055, t_return=2.075,
                             prefill_rows=[(40, 256)])]
    runs = tr.step_programs(t, steps, 2.0, ("pallas_call",))
    assert [e.start for e in runs["decode"]] == [10 * MS]
    assert [e.start for e in runs["prefill"]] == [60 * MS]
    assert [e.start for e in runs["other"]] == [90 * MS]


def test_without_program_ids_a_run_holding_the_kernel_is_decode():
    t = hand_trace()
    for lines in t.devices.values():
        for line, evs in lines.items():
            lines[line] = [tr.Event(e.name, e.start, e.end, None, e.text)
                           for e in evs]
    steps = [SimpleNamespace(t_call=2.055, t_return=2.075,
                             prefill_rows=[(40, 256)])]
    runs = tr.step_programs(t, steps, 2.0, ("pallas_call",))
    assert [e.start for e in runs["decode"]] == [10 * MS]
    assert [e.start for e in runs["prefill"]] == [60 * MS]


def test_idle_gaps_are_named_by_the_host_span_covering_them():
    total = {}
    for name, secs in hand_trace().idle_gaps():
        total[name] = total.get(name, 0.0) + secs
    # [0,10] and [25,30] lie in step_fn as much as outside any span;
    # [35,60] is 8 ms in bench spans and 17 ms outside; [70,100] outside
    assert total["bench.step_fn"] == pytest.approx(0.015)
    assert total["program"] == pytest.approx(0.055)
    assert "bench.submit" not in total


def test_loop_ops_count_for_busy_time_but_not_as_operations():
    loop = tr.Event("%while.12 = (s32[]{:T(128)}, bf16[4]{0}) while("
                    "(s32[]{:T(128)}, bf16[4]{0}) %t), condition=%c",
                    0, 50 * MS, DECODE)
    fusion = tr.Event("%fusion.3 = bf16[4]{0:T(128)} fusion(%a), kind=kLoop",
                      10 * MS, 20 * MS, DECODE)
    t = tr.Trace([("bench.window_start", 0, 1),
                  ("bench.window_end", 100 * MS, 100 * MS)],
                 {"/device:TPU:0": {tr.OPS_LINE: [loop, fusion]}})
    assert tr.op_kind(loop.name) == "while"
    assert t.op_seconds() == {"%fusion.3 fusion": pytest.approx(0.010)}
    assert t.busy_s() == pytest.approx(0.050)


def test_union_and_gaps_helpers():
    assert tr.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert tr.gaps([(1, 4), (5, 6)], 0, 8) == [(0, 1), (4, 5), (6, 8)]


# 0.6 s of smollm2.pff-sweep.shared-doc on one TPU v5 lite: one 32-row
# admission (host step 2.0) and one decode step, with the harness's spans
RECORDED = DATA / "smollm2-shared-doc-0.6s.xplane.pb"


def test_recorded_tpu_trace_reduces():
    t = tr.Trace.from_file(str(RECORDED))
    assert list(t.devices) == ["/device:TPU:0"]
    assert 0.6 < t.window_s < 0.8
    assert 0 < t.busy_s() <= t.window_s
    # leaf ops cover the busy time (the loop ops around them add µs)
    assert sum(t.op_seconds().values()) == pytest.approx(t.busy_s(),
                                                         abs=1e-4)
    kernel = t.matching_ops("pallas_call", "tpu_custom_call")
    assert len(kernel) == 48                     # 24 layers x 2 calls
    assert all(tr.op_kind(e.name) == "custom-call" for e in kernel)
    # the decode runs hold the kernel, the admission's run does not
    runs = tr.step_programs(t, [], 0.0, ("pallas_call", "tpu_custom_call"))
    assert len(runs["decode"]) == 2 and len(runs["other"]) == 1
    assert all(
        any(r.start <= k.start <= r.end for k in kernel)
        for r in runs["decode"])
    names = {n for n, _s in t.idle_gaps()}
    assert names <= {"bench.step_fn", "bench.submit", "program"}
