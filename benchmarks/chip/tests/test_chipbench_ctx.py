"""What a per-layer reader is given in a traced run, on the CPU at a
small size: the window's program spans that it declares, and the
window's increase of the program's ``repro.obs`` counters.  Both probes
are added to the small root as new files, the way a later change adds a
reader."""

from __future__ import annotations

import io
import json

import pytest

from benchmarks.chip import manifest, run, trace
from chipbench_fixtures import small_root

CELL = "tiny-syrmi.tiny-uniform"
SEED = 2**31 + 123
SPAN = "probe.call"
COUNTER = "chipbench_probe_calls"

SPAN_READER = f'''
SPANS = ({SPAN!r},)


def read(ctx):
    return ((ctx.get("trace") or {{}}).get("span_s") or {{}}).get({SPAN!r})
'''
COUNTER_READER = f'''
from benchmarks.chip.readers import counter


def read(ctx):
    return counter(ctx, {COUNTER!r}, cell="probe")
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = small_root(tmp_path_factory.mktemp("bench"))
    layers = root / "benchmarks" / "chip" / "layers"
    (layers / "probe_span_s.py").write_text(SPAN_READER)
    (layers / "probe_calls.py").write_text(COUNTER_READER)
    man = manifest.load(root)
    for name, unit in (("probe_span_s", "s"), ("probe_calls", "calls")):
        man["per_layer"].append({"name": name, "unit": unit, "better": "lower",
                                 "source": "program_span", "layer": "probe", "moves": "ops_per_s",
                                 "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    assert manifest.problems(root) == []
    return root


def probed(real):
    """``real`` (an entry module) with every call inside the span ``SPAN``
    and counted by ``COUNTER``, as the program's own spans and counters are."""
    from repro import obs

    calls = obs.default_registry().counter(COUNTER, labels=("cell",))

    class Probed:
        @staticmethod
        def build(cfg, table):
            e = real.build(cfg, table)
            inner = e.call

            def call(q):
                with obs.span(SPAN):
                    calls.inc(cell="probe")
                    return inner(q)

            e.call = call
            return e

    return Probed


def test_a_reader_sees_its_spans_and_the_window_s_counter_increase(root):
    c = manifest.cell(root, CELL)
    out = io.StringIO()
    r = run.run(CELL, SEED, 0.3, True, root=root, require_chip=False,
                entry_module=probed(c["entry"]), out=out)
    assert r["correct"]
    requests = r["attempted"] // int(c["mix"]["batch"])
    # the warm-up's calls come before the window and are not counted
    assert requests > run.WARMUP_CALLS
    assert r["metrics"]["probe_calls"] == {"value": requests, "unit": "calls"}
    lines = [json.loads(x) for x in out.getvalue().splitlines()]
    phase = {x["phase"]: x for x in lines}
    window_s = phase["trace"]["window_s"]
    assert 0 < r["metrics"]["probe_span_s"]["value"] < window_s
    assert phase["trace"]["span_s"][SPAN] == r["metrics"]["probe_span_s"]["value"]
    assert [COUNTER, {"cell": "probe"}, requests] in phase["trace"]["counters"]
    # a traced run compiles its programs afresh, with their scopes
    assert phase["device"]["compile_cache"].startswith("bypassed")
    # the CPU has no device plane: no device metric is read
    assert {"device_idle_pct", "lookup_device_ms", "lookup_predict_device_ms"}.isdisjoint(r["metrics"])


def test_an_untraced_run_reads_no_counters_and_keeps_its_cache(root):
    c = manifest.cell(root, CELL)
    out = io.StringIO()
    r = run.run(CELL, SEED, 0.2, False, root=root, require_chip=False,
                entry_module=probed(c["entry"]), out=out)
    assert r["correct"] and "probe_calls" not in r["metrics"]
    phase = {x["phase"]: x for x in map(json.loads, out.getvalue().splitlines())}
    assert "trace" not in phase
    assert not phase["device"]["compile_cache"].startswith("bypassed")


@pytest.mark.parametrize("cell,extra", [
    ("tiny-syrmi.tiny-uniform", ()),
    ("tiny-pgmm-tier4.tiny-zipf", ("tier.telemetry", "tier.telemetry.pull", "tier.telemetry.record")),
])
def test_a_cell_collects_the_spans_its_readers_declare(tmp_path, cell, extra):
    root = small_root(tmp_path)
    names = trace.collected_spans(manifest.cell(root, cell)["per_layer"].values())
    assert names == (*trace.HOST_SPANS, *extra)
