"""The scope and idle-span reduction (``scopes.py``) on hand-made event
lists with known answers, and on a small trace recorded on a TPU v5e."""

from __future__ import annotations

import gzip
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import manifest, scopes, trace
from chipbench_fixtures import REPO

MS = 1_000_000  # ns
RECORDED = Path(__file__).parent / "data" / "tier4-8m-keys.xplane.pb.gz"
READERS = {name: manifest.load_module(manifest.bench_file(REPO, "layers", name, ".py"))
           for name in ("lookup_predict_device_ms", "lookup_search_device_ms",
                        "lookup_unscoped_device_ms", "tier_sync_idle_pct", "tier_device_ms")}
TIER_SPANS = READERS["tier_sync_idle_pct"].SPANS
#: the host spans the tier cell's readers collect
TIER_NAMES = trace.collected_spans(READERS.values())


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
    r = scopes.reduce(chips, spans, (0, 10 * MS), names=TIER_NAMES)
    by = r["idle_by_span"]
    # chip 0 idle [2,6] [7,10], chip 1 idle [4,10], averaged over the two chips
    assert by["tier.telemetry.pull"] == pytest.approx((0.002 + 0.001) / 2)
    assert by["tier.telemetry"] == pytest.approx((0.001 + 0.001) / 2)
    assert by["entry_call"] == pytest.approx((0.001 + 0.002 + 0.003) / 2)
    assert by["other"] == pytest.approx((0.001 + 0.001) / 2)
    idle_s = trace.reduce(chips, spans, (0, 10 * MS))["idle_pct"] / 100 * 0.010
    assert sum(by.values()) == pytest.approx(idle_s)
    assert r["idle_gaps"][0] == ["entry_call", pytest.approx(0.006)]  # chip 1's [4,10]
    assert ["tier.telemetry.pull", pytest.approx(0.004)] in r["idle_gaps"]  # chip 0's [2,6]


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


#: the reduction's keys on the recorded trace before it was one reduction
#: (``trace.reduce`` of the parent commit, host spans ``trace.HOST_SPANS``)
PARENT_KEYS = {
    "busy_s": 0.18385662600000002, "window_s": 0.226874118, "idle_pct": 18.96095172918755,
    "programs": {"jit__lookup_vmapped": 0.18086175699999998, "jit__owner_histogram": 0.002965798,
                 "jit_equal": 3.375e-05},
    "device_ops": [["%while.9", 0.06799691499999999], ["%while.8", 0.06799665099999999],
                   ["%fusion.235", 0.033956914000000026], ["%fusion.236", 0.03395652999999997],
                   ["%fusion.240", 0.03395651599999998], ["%fusion.239", 0.03395625399999998],
                   ["%fusion", 0.0029351520000000008], ["%fusion.36", 0.002124654],
                   ["%fusion.26", 0.002122943], ["%fusion.20", 0.0021223]],
    "idle_gaps": [["entry_call", 0.0029200320000000004], ["entry_call", 0.0026634690000000003],
                  ["entry_call", 0.002265081], ["entry_call", 0.002263579],
                  ["entry_call", 0.002232249], ["entry_call", 0.002102671],
                  ["entry_call", 0.002095819], ["entry_call", 0.0020804160000000003],
                  ["entry_call", 0.0020360580000000003], ["entry_call", 0.002005189]],
}
RECORDED_REQUESTS = 12


def approx(value):
    """``pytest.approx`` to 1e-9, into ``[name, seconds]`` lists."""
    if isinstance(value, list):
        return [[name, pytest.approx(v, rel=1e-9)] for name, v in value]
    return pytest.approx(value, rel=1e-9)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tier.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    return path


def test_recorded_tier_trace(recorded):
    """A 12-request window of the 4-shard PGM_M tier at 8M keys, traced on
    a TPU v5e: the keys the reduction had before it took scopes and
    program spans read as they did, and the scopes and spans account for
    every busy and idle second."""
    chips, spans, window, op_scopes = trace.load(str(recorded))
    old = trace.reduce(chips, spans, window)
    for key, want in PARENT_KEYS.items():
        assert old[key] == approx(want), key

    chips, spans, window, op_scopes = trace.load(str(recorded), TIER_NAMES)
    assert op_scopes["jit__lookup_vmapped(723856815974040453)"]["%while.8"] == "predict"
    assert op_scopes["jit__lookup_vmapped(723856815974040453)"]["%while.9"] == "search"
    r = trace.reduce(chips, spans, window, op_scopes, TIER_NAMES)
    for key in ("busy_s", "window_s", "idle_pct", "programs", "device_ops"):
        assert r[key] == approx(PARENT_KEYS[key]), key
    device_s = sum(r["scopes"].values()) + sum(r["unscoped"].values())
    assert device_s == pytest.approx(old["busy_s"], rel=1e-9)
    assert r["scopes"]["predict"] > 0 and r["scopes"]["search"] > 0
    top = [name for name, _ in r["unscoped_ops"][:3]]
    assert "%custom-call.18 X64SplitHigh" in top and "%custom-call.19 X64SplitLow" in top
    idle_s = old["window_s"] - old["busy_s"]
    assert sum(r["idle_by_span"].values()) == pytest.approx(idle_s, rel=1e-9)
    assert set(TIER_SPANS) <= set(r["idle_by_span"]) <= {*TIER_NAMES, "other"}
    assert any(name == "tier.telemetry.pull" for name, _ in r["idle_gaps"])
    assert 0 < r["span_s"]["tier.telemetry.pull"] < r["span_s"]["tier.telemetry"] < r["span_s"]["entry_call"]


def test_the_scope_readers_read_what_the_command_line_prints(recorded):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "benchmarks/chip/scopes.py", str(recorded)], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=120, check=True)
    cli = json.loads(p.stdout.splitlines()[-1])
    chips, spans, window, op_scopes = trace.load(str(recorded), TIER_NAMES)
    ctx = {"trace": trace.reduce(chips, spans, window, op_scopes, TIER_NAMES),
           "requests": RECORDED_REQUESTS}
    got = {name: mod.read(ctx) for name, mod in READERS.items()}
    per_request = 1e3 / RECORDED_REQUESTS
    unscoped = READERS["lookup_unscoped_device_ms"].PROGRAMS
    assert got == {
        "lookup_predict_device_ms": pytest.approx(cli["scopes"]["predict"] * per_request, rel=1e-12),
        "lookup_search_device_ms": pytest.approx(cli["scopes"]["search"] * per_request, rel=1e-12),
        "lookup_unscoped_device_ms": pytest.approx(
            sum(v for k, v in cli["unscoped"].items() if any(n in k for n in unscoped)) * per_request,
            rel=1e-12),
        "tier_sync_idle_pct": pytest.approx(
            100 * sum(cli["idle_by_span"][n] for n in TIER_SPANS) / cli["window_s"], rel=1e-12),
        "tier_device_ms": pytest.approx(
            (cli["programs"]["jit__lookup_vmapped"] + cli["programs"]["jit__owner_histogram"])
            * per_request, rel=1e-12),
    }
    # the scopes and the unscoped rest make up the tier's programs
    split = sum(got[n] for n in ("lookup_predict_device_ms", "lookup_search_device_ms",
                                 "lookup_unscoped_device_ms"))
    assert split == pytest.approx(got["tier_device_ms"], rel=1e-3)
    assert got["tier_sync_idle_pct"] <= cli["idle_pct"]

    # a program compiled without its scopes, and a cell that collects no tier span
    bare = trace.reduce(chips, spans, window, {}, trace.HOST_SPANS)
    assert bare["scopes"] == {}
    assert {name: mod.read({"trace": bare, "requests": RECORDED_REQUESTS})
            for name, mod in READERS.items() if name != "tier_device_ms"} == dict.fromkeys(
        ("lookup_predict_device_ms", "lookup_search_device_ms", "lookup_unscoped_device_ms",
         "tier_sync_idle_pct"))


def test_a_trace_without_scopes_or_program_spans():
    chips = {0: {"ops": [("%fusion.1 = f32[4] fusion()", 0, 2 * MS)], "modules": [("jit_f(1)", 0, 2 * MS)]}}
    r = scopes.reduce(chips, [("entry_call", 0, 4 * MS)], (0, 4 * MS), {}, TIER_NAMES)
    assert r["scopes"] == {}
    assert r["unscoped"] == {"jit_f": pytest.approx(0.002)}
    assert not set(TIER_SPANS) & set(r["idle_by_span"])
    assert scopes.reduce({}, [], (0, 1)) == {"window_s": pytest.approx(1e-9), "span_s": {}}
    assert scopes.reduce({}, [], (1, 1)) == {}
