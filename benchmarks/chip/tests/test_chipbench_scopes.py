"""The scope and idle-span reduction (``scopes.py``) on hand-made event
lists with known answers, and on a small trace recorded on a TPU v5e."""

from __future__ import annotations

import gzip
import time
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import scopes, trace

MS = 1_000_000  # ns
RECORDED = Path(__file__).parent / "data" / "tier4-8m-keys.xplane.pb.gz"


def test_scope_union_counts_a_while_and_its_body_once():
    # %while.4 (search) holds its body's gathers; %fusion.1 is predict;
    # the table's limb split is under no scope
    chips = {0: {
        "ops": [("%custom-call.16 = u32[8] custom-call(u64[8] %table), custom_call_target=\"X64SplitHigh\"",
                 0, 2 * MS),
                ("%fusion.1 = f32[4] fusion()", 2 * MS, 3 * MS),
                ("%while.4 = (u32[]) while()", 3 * MS, 9 * MS),
                ("%fusion.186 = u32[4] fusion()", 3 * MS, 5 * MS),
                ("%fusion.187 = u32[4] fusion()", 6 * MS, 8 * MS)],
        "modules": [("jit__lookup_jit(7)", 0, 9 * MS)],
    }}
    op_scopes = {"jit__lookup_jit(7)": {"%fusion.1": "predict", "%while.4": "search",
                                        "%fusion.186": "search", "%fusion.187": "search"}}
    r = scopes.reduce(chips, [], (0, 10 * MS), op_scopes)
    assert r["scopes"] == {"predict": pytest.approx(0.001), "search": pytest.approx(0.006)}
    assert r["unscoped"] == {"jit__lookup_jit": pytest.approx(0.002)}
    assert r["unscoped_ops"] == [["%custom-call.16 X64SplitHigh", pytest.approx(0.002)]]
    busy = trace.reduce(chips, [], (0, 10 * MS))["busy_s"]
    assert sum(r["scopes"].values()) + sum(r["unscoped"].values()) == pytest.approx(busy)


def test_unscoped_time_inside_a_scoped_op_is_not_counted():
    # a compiler-made copy with no metadata inside the search loop is search time
    chips = {0: {"ops": [("%while.1 = () while()", 0, 4 * MS), ("%copy.3 = () copy()", 1 * MS, 2 * MS),
                         ("%copy.9 = () copy()", 5 * MS, 6 * MS)],
                 "modules": [("jit_f(1)", 0, 6 * MS)]}}
    r = scopes.reduce(chips, [], (0, 6 * MS), {"jit_f(1)": {"%while.1": "search"}})
    assert r["scopes"]["search"] == pytest.approx(0.004)
    assert r["unscoped"]["jit_f"] == pytest.approx(0.001)


def test_scope_of_reads_bare_and_wrapped_components():
    assert scopes.scope_of("jit(_lookup_jit)/search/while") == "search"
    assert scopes.scope_of("jit(_lookup_vmapped)/vmap(predict)/jit(_take)/add") == "predict"
    assert scopes.scope_of("jit(_lookup_jit)/research/add") is None
    assert scopes.scope_of("table") is None


def test_idle_goes_to_the_innermost_span_and_sums_to_the_idle_time():
    chips = {0: {"ops": [("a", 0, 2 * MS), ("b", 6 * MS, 7 * MS)], "modules": []},
             1: {"ops": [("a", 0, 4 * MS)], "modules": []}}
    spans = [("entry_call", 1 * MS, 9 * MS), ("tier.telemetry", 3 * MS, 6 * MS),
             ("tier.telemetry.pull", 3 * MS, 5 * MS), ("unrelated", 0, 10 * MS)]
    r = scopes.reduce(chips, spans, (0, 10 * MS))
    by = r["idle_by_span"]
    # chip 0 idle [2,6] [7,10], chip 1 idle [4,10], averaged over the two chips
    assert by["tier.telemetry.pull"] == pytest.approx((0.002 + 0.001) / 2)
    assert by["tier.telemetry"] == pytest.approx((0.001 + 0.001) / 2)
    assert by["entry_call"] == pytest.approx((0.001 + 0.002 + 0.003) / 2)
    assert by["other"] == pytest.approx((0.001 + 0.001) / 2)
    idle_s = trace.reduce(chips, spans, (0, 10 * MS))["idle_pct"] / 100 * 0.010
    assert sum(by.values()) == pytest.approx(idle_s)
    assert r["idle_gaps"][0] == ["entry_call", pytest.approx(0.006)]  # chip 1's [4,10]
    assert ["entry_call/tier.telemetry.pull", pytest.approx(0.004)] in r["idle_gaps"]  # chip 0's [2,6]


def test_span_pieces_nest_by_time():
    spans = [("entry_call", 0, 10), ("tier.telemetry", 2, 8), ("tier.telemetry.pull", 2, 5),
             ("tier.telemetry.record", 5, 8), ("answer_to_host", 12, 14)]
    assert scopes.span_pieces(spans) == [
        (0, 2, ("entry_call",)),
        (2, 5, ("entry_call", "tier.telemetry", "tier.telemetry.pull")),
        (5, 8, ("entry_call", "tier.telemetry", "tier.telemetry.record")),
        (8, 10, ("entry_call",)),
        (12, 14, ("answer_to_host",)),
    ]


def test_the_sweep_names_a_million_gaps_in_seconds():
    rng = np.random.default_rng(0)
    starts = np.sort(rng.choice(10**9, size=10**6, replace=False)).astype(float) * 100
    gaps = [(s, s + 50.0) for s in starts]
    span_starts = np.sort(rng.choice(10**7, size=10**5, replace=False)).astype(float) * 10**4
    spans = [("entry_call", s, s + 5000.0) for s in span_starts]
    t = time.perf_counter()
    pieces = scopes.span_pieces(spans)
    by = scopes.idle_by_span(gaps, pieces)
    took = time.perf_counter() - t
    assert sum(by.values()) == pytest.approx(50.0 * len(gaps))
    assert took < 10.0, f"{took:.1f} s"


def test_recorded_tier_trace(tmp_path):
    """A 12-request window of the 4-shard PGM_M tier at 8M keys, traced on
    a TPU v5e: the old reduction reads what it read before this module
    existed, and the new one accounts for every busy and idle second."""
    path = tmp_path / "tier.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    chips, spans, window = trace.load(str(path))
    old = trace.reduce(chips, spans, window)
    assert old["busy_s"] == pytest.approx(0.183856626, rel=1e-9)
    assert old["idle_pct"] == pytest.approx(18.96095172918755, rel=1e-9)
    assert old["programs"] == pytest.approx({"jit__lookup_vmapped": 0.180861757,
                                             "jit__owner_histogram": 0.002965798,
                                             "jit_equal": 3.375e-05}, rel=1e-9)
    assert old["idle_gaps"][0] == ["entry_call", pytest.approx(0.002920032, rel=1e-9)]

    chips, spans, window, op_scopes = scopes.load(str(path))
    assert op_scopes["jit__lookup_vmapped(723856815974040453)"]["%while.8"] == "predict"
    assert op_scopes["jit__lookup_vmapped(723856815974040453)"]["%while.9"] == "search"
    r = scopes.reduce(chips, spans, window, op_scopes)
    device_s = sum(r["scopes"].values()) + sum(r["unscoped"].values())
    assert device_s == pytest.approx(old["busy_s"], rel=1e-9)
    assert r["scopes"]["predict"] > 0 and r["scopes"]["search"] > 0
    top = [name for name, _ in r["unscoped_ops"][:3]]
    assert "%custom-call.18 X64SplitHigh" in top and "%custom-call.19 X64SplitLow" in top
    idle_s = old["window_s"] - old["busy_s"]
    assert sum(r["idle_by_span"].values()) == pytest.approx(idle_s, rel=1e-9)
    assert set(scopes.PROGRAM_SPANS) <= set(r["idle_by_span"])
    assert any(name == "entry_call/tier.telemetry.pull" for name, _ in r["idle_gaps"])


def test_a_trace_without_scopes_or_program_spans():
    chips = {0: {"ops": [("%fusion.1 = f32[4] fusion()", 0, 2 * MS)], "modules": [("jit_f(1)", 0, 2 * MS)]}}
    r = scopes.reduce(chips, [("entry_call", 0, 4 * MS)], (0, 4 * MS), {})
    assert r["scopes"] == {}
    assert r["unscoped"] == {"jit_f": pytest.approx(0.002)}
    assert not set(scopes.PROGRAM_SPANS) & set(r["idle_by_span"])
    assert scopes.reduce({}, [], (0, 1)) == {}
