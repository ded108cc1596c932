"""The trace reduction on a hand-made event list with known answers."""

from __future__ import annotations

import pytest

from benchmarks.chip import trace

MS = 1_000_000  # ns


def test_busy_is_the_union_of_op_intervals():
    ops = [(0, 2 * MS), (1 * MS, 3 * MS), (5 * MS, 6 * MS), (9 * MS, 12 * MS)]
    assert trace.busy_ns(ops, 0, 10 * MS) == 5 * MS  # [0,3] + [5,6] + [9,10]
    assert trace.idle_gaps(ops, 0, 10 * MS) == [(3 * MS, 5 * MS), (6 * MS, 9 * MS)]


def test_reduce_gives_busy_idle_programs_and_named_gaps():
    chips = {
        0: {
            "ops": [("fusion.1", 1 * MS, 3 * MS), ("gather.2", 3 * MS, 4 * MS),
                    ("fusion.1", 6 * MS, 8 * MS)],
            "modules": [("jit__lookup_jit(41)", 1 * MS, 4 * MS),
                        ("jit__lookup_jit(41)", 6 * MS, 8 * MS),
                        ("jit_equal(7)", 9 * MS, 9 * MS + MS // 2)],
        }
    }
    spans = [("entry_call", 0, 1 * MS), ("answer_to_host", 4 * MS, 6 * MS),
             ("make_request", 8 * MS, 8 * MS + MS // 4), ("entry_call", 8 * MS + MS // 4, 10 * MS),
             ("unrelated", 0, 10 * MS)]
    r = trace.reduce(chips, spans, (0, 10 * MS))
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.005)
    assert r["idle_pct"] == pytest.approx(50.0)
    assert r["programs"]["jit__lookup_jit"] == pytest.approx(0.005)
    assert r["programs"]["jit_equal"] == pytest.approx(0.0005)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.004)]
    assert r["device_ops"][1] == ["gather.2", pytest.approx(0.001)]
    # gaps: [0,1] entry_call, [4,6] answer_to_host, [8,10] mostly entry_call
    assert r["idle_gaps"] == [["answer_to_host", pytest.approx(0.002)],
                              ["entry_call", pytest.approx(0.002)],
                              ["entry_call", pytest.approx(0.001)]]


def test_two_chips_average_busy_and_clip_to_the_window():
    chips = {
        0: {"ops": [("a", -2 * MS, 2 * MS)], "modules": []},
        1: {"ops": [("a", 0, 4 * MS)], "modules": []},
    }
    r = trace.reduce(chips, [], (0, 4 * MS))
    assert r["busy_s"] == pytest.approx(0.003)  # (2 + 4) / 2 ms
    assert r["idle_gaps"] == [["other", pytest.approx(0.002)]]
    assert trace.reduce({}, [], (0, 1)) == {}


def test_names_drop_run_ids_and_hlo_text():
    assert trace.program_name("jit__lookup_vmapped(123)") == "jit__lookup_vmapped"
    assert trace.program_name("jit_equal") == "jit_equal"
    assert trace.op_name("%fusion.187 = u32[262144]{0:T(1024)} fusion(u32[8] %a), kind=kCustom") == "%fusion.187"
    assert trace.op_name("copy.7") == "copy.7"
