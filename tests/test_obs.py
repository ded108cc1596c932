"""repro.obs — the unified metrics/tracing/profiling layer.

Four layers of coverage:

* **registry unit tests** — counters/gauges/histograms on a private
  ``Registry`` (no global state), snapshot/diff/JSONL round-trips,
  quantile math;
* **overhead gates** — telemetry-on ``sharded_lookup`` adds at most ONE
  new jitted trace (the owner histogram) and never perturbs the lookup
  traces; telemetry-off lookups import nothing from ``repro.obs``;
* **view parity** — ``tier_metrics()`` / ``TunedTier.metrics()`` /
  ``DecodeEngine.metrics()`` render from registry snapshots but keep
  their PR 2/6 shapes, and the PR 8 regressions
  (``derived_tier_metrics({})``, sink-reset ownership) stay fixed;
* **harness smoke** — ``serve_slo.check_slo`` gates and the
  ``python -m repro.obs`` CLI.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import repro
from repro import index as ix
from repro import obs
from repro.dist import sharded_index as si
from repro.obs import registry as obs_registry
from repro.obs.timing import span, stopwatch, timed_lookup

from conftest import make_queries, make_table

ROOT = Path(__file__).resolve().parents[1]
N = 2048
LATENCY_LABELS = {"kind": "RMI", "backend": "xla", "tier": "obs_test", "phase": "host"}


def fresh_registry() -> obs_registry.Registry:
    return obs_registry.Registry()


# ---------------------------------------------------------------------------
# Registry unit tests (private registry: no global state)
# ---------------------------------------------------------------------------


def test_counter_gauge_basics():
    reg = fresh_registry()
    c = reg.metric("route_queries")  # catalogue-backed: labels=("tier",)
    c.inc(3, tier="a")
    c.inc(4, tier="a")
    c.inc(1, tier="b")
    assert c.value(tier="a") == 7.0
    assert c.value(tier="b") == 1.0
    with pytest.raises(ValueError):
        c.inc(1, wrong_label="a")
    g = reg.metric("tier_pending")
    g.set(5, tier="a")
    g.set(2, tier="a")
    assert g.value(tier="a") == 2.0
    g.max(9, tier="a")
    g.max(4, tier="a")
    assert g.value(tier="a") == 9.0


def test_metric_catalogue_names_are_closed():
    reg = fresh_registry()
    with pytest.raises(KeyError):
        reg.metric("not_a_registered_metric")
    # every catalogue entry materialises with its declared type
    for name, mtype, _labels, desc in obs.metric_catalogue():
        m = reg.metric(name)
        assert type(m).__name__.lower() == mtype
        assert desc


def test_histogram_observe_and_quantiles():
    reg = fresh_registry()
    h = reg.histogram("obs_test_us", labels=("name",), edges=[1.0, 10.0, 100.0])
    for v in (0.5, 5.0, 5.0, 50.0, 500.0):
        h.observe(v, name="t")
    snap = reg.snapshot()
    s = obs.find_sample(snap, "obs_test_us", name="t")
    assert s["count"] == 5
    assert s["counts"] == [1, 2, 1, 1]
    assert s["sum"] == pytest.approx(560.5)
    # quantiles: interpolated within buckets, saturating at the top edge
    assert 0.0 < obs.hist_quantile(s, 0.5) <= 10.0
    assert obs.hist_quantile(s, 0.99) == pytest.approx(100.0)
    empty = {"edges": [1.0, 10.0], "counts": [0, 0, 0], "count": 0, "sum": 0.0}
    assert obs.hist_quantile(empty, 0.5) == 0.0


def test_histogram_edges_must_increase():
    reg = fresh_registry()
    with pytest.raises(ValueError):
        reg.histogram("obs_test_us", edges=[10.0, 1.0])


def test_exp_edges_and_default_latency_edges():
    e = obs_registry.exp_edges(1.0, 1000.0, 4)
    assert e[0] == pytest.approx(1.0) and e[-1] == pytest.approx(1000.0)
    assert all(b > a for a, b in zip(e, e[1:]))
    d = obs_registry.DEFAULT_LATENCY_EDGES
    assert d[0] == pytest.approx(1.0) and d[-1] == pytest.approx(1e7)


def test_snapshot_diff_counters_subtract_gauges_latch():
    reg = fresh_registry()
    reg.metric("route_queries").inc(10, tier="a")
    reg.metric("tier_pending").set(3, tier="a")
    before = reg.snapshot()
    reg.metric("route_queries").inc(5, tier="a")
    reg.metric("tier_pending").set(8, tier="a")
    after = reg.snapshot()
    d = obs.diff(before, after)
    assert obs.sample_value(d, "route_queries", tier="a") == 5.0
    assert obs.sample_value(d, "tier_pending", tier="a") == 8.0


def test_jsonl_round_trip_is_stable():
    reg = fresh_registry()
    reg.metric("route_queries").inc(4, tier="a")
    reg.metric("lookup_latency_us").observe(5.0, **LATENCY_LABELS)
    snap = reg.snapshot()
    text = obs.to_jsonl(snap)
    for line in text.strip().splitlines():
        row = json.loads(line)  # one valid JSON object per line
        assert {"name", "type", "labels"} <= set(row)
    back = obs.from_jsonl(text)
    assert obs.sample_value(back, "route_queries", tier="a") == 4.0
    assert obs.find_sample(back, "lookup_latency_us", **LATENCY_LABELS)["count"] == 1
    assert obs.to_jsonl(back) == text


def test_reset_prefix_only_clears_that_family():
    reg = fresh_registry()
    reg.metric("route_queries").inc(4, tier="a")
    reg.metric("tier_lookups").inc(2, tier="a")
    reg.reset(prefix="route_")
    snap = reg.snapshot()
    assert obs.sample_value(snap, "route_queries", tier="a", default=0.0) == 0.0
    assert obs.sample_value(snap, "tier_lookups", tier="a") == 2.0


def traced_spans(trace_dir: Path, body, names: tuple) -> dict:
    """Run ``body`` under a CPU profiler trace and read back the host
    spans named ``names`` from its ``.xplane.pb``: name -> [(start, end)]."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(trace_dir))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    spans = {n: [] for n in names}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in spans:
                    spans[e.name].append((e.start_ns, e.end_ns))
    return spans


def test_span_and_stopwatch_record(tmp_path):
    """A span is a profiler annotation: nested spans land in the trace,
    the child inside its parent; outside a trace a span records nothing."""
    before = obs.snapshot()
    sw = stopwatch()
    with span("obs_test.untraced"):  # no trace active: a no-op
        pass

    def body():
        with span("obs_test.outer"):
            with span("obs_test.inner"):
                sw.restart()
                while sw.elapsed < 1e-3:
                    pass

    got = traced_spans(tmp_path, body, ("obs_test.outer", "obs_test.inner", "obs_test.untraced"))
    (outer,), (inner,) = got["obs_test.outer"], got["obs_test.inner"]
    assert outer[0] <= inner[0] < inner[1] <= outer[1]
    assert inner[1] - inner[0] >= 1e6  # ns: the busy millisecond
    assert got["obs_test.untraced"] == []
    assert obs.diff(before, obs.snapshot()) == obs.diff(before, before)  # no registry write


def test_tier_lookup_writes_its_telemetry_spans(tmp_path):
    """One ``TunedTier.lookup`` writes ``tier.telemetry`` once, holding
    one ``tier.telemetry.pull`` followed by one ``tier.telemetry.record``."""
    from repro.tune.rebuild import TunedTier

    rng = np.random.default_rng(14)  # not the session stream: leaves other tests' tables alone
    table = make_table(rng, "uniform", N)
    qs = make_queries(rng, table, 256)
    tier = TunedTier(table, n_shards=4, spec=ix.RMISpec(b=64))
    tier.lookup(qs)  # compile outside the trace
    names = ("tier.telemetry", "tier.telemetry.pull", "tier.telemetry.record")
    got = traced_spans(tmp_path, lambda: np.asarray(tier.lookup(qs)), names)
    (tel,), (pull,), (rec,) = (got[n] for n in names)
    assert tel[0] <= pull[0] < pull[1] <= rec[0] < rec[1] <= tel[1]


# ---------------------------------------------------------------------------
# Overhead gates: traces and imports
# ---------------------------------------------------------------------------


def test_telemetry_on_adds_at_most_one_trace(rng):
    """Telemetry-on sharded lookups leave the shared lookup traces
    untouched and add at most one jitted dispatch (the owner
    histogram); timed_lookup adds only the single histogram-update
    trace."""
    table = make_table(rng, "uniform", N)
    qs = make_queries(rng, table, 512)
    sidx = si.ShardedIndex.build("RMI", table, n_shards=4, b=64)
    si.sharded_lookup(sidx, qs)  # telemetry-off: prime the lookup trace
    before = dict(ix.trace_counts())

    si.sharded_lookup(sidx, qs, telemetry=True)
    after = dict(ix.trace_counts())
    lookup_keys = {k for k in before if not k[0].startswith("obs:")}
    assert {k: after[k] for k in lookup_keys} == {k: before[k] for k in lookup_keys}
    new = {k: v for k, v in after.items() if k not in before}
    assert set(new) <= {("obs:owner_hist", "jit")}
    assert sum(new.values()) <= 1

    idx = ix.build(ix.RMISpec(b=64), table)
    idx.lookup(table, qs)  # prime
    before = dict(ix.trace_counts())
    out = timed_lookup(idx, table, qs, tier="obs_test")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(idx.lookup(table, qs)))
    after = dict(ix.trace_counts())
    new = {k: v for k, v in after.items() if after[k] != before.get(k, 0)}
    assert set(new) <= {("obs:hist", "update")}


def test_telemetry_off_paths_never_import_obs(rng):
    """With ``repro.obs`` evicted, telemetry-off ``Index.lookup`` and
    ``sharded_lookup`` complete without re-importing it — the hot path
    has zero obs surface unless telemetry is requested."""
    table = make_table(rng, "uniform", N)
    qs = make_queries(rng, table, 256)
    idx = ix.build(ix.RMISpec(b=64), table)
    sidx = si.ShardedIndex.build("RMI", table, n_shards=4, b=64)

    saved = {k: sys.modules.pop(k) for k in list(sys.modules) if k.startswith("repro.obs")}
    saved_attr = repro.__dict__.pop("obs", None)
    try:
        idx.lookup(table, qs)
        si.sharded_lookup(sidx, qs, telemetry=False)
        leaked = [k for k in sys.modules if k.startswith("repro.obs")]
        assert not leaked, f"telemetry-off lookup imported {leaked}"
    finally:
        sys.modules.update(saved)
        if saved_attr is not None:
            repro.obs = saved_attr


# ---------------------------------------------------------------------------
# View parity: the old surfaces render from registry snapshots
# ---------------------------------------------------------------------------


def test_derived_tier_metrics_tolerates_empty_and_zero():
    m = si.derived_tier_metrics({})
    assert m["queries"] == 0
    assert m["drop_rate"] == 0.0
    assert m["imbalance_mean"] == 0.0
    m = si.derived_tier_metrics(
        {"queries": 100, "dropped": 1, "routed_max": 50, "routed_even": 25.0}
    )
    assert m["drop_rate"] == pytest.approx(0.01)
    assert m["imbalance_mean"] == pytest.approx(2.0)


def test_reset_tier_metrics_leaves_caller_sink_alone(rng):
    table = make_table(rng, "uniform", N)
    qs = make_queries(rng, table, 256)
    n_q = len(qs)
    sidx = si.ShardedIndex.build("RMI", table, n_shards=4, b=64)
    sink = si._fresh_tier_metrics()
    si.sharded_lookup(sidx, qs, telemetry=True, telemetry_sink=sink)
    assert sink["queries"] == n_q
    si.reset_tier_metrics()
    # the registry aggregate resets; the caller-owned sink is untouched
    assert si.tier_metrics()["queries"] == 0
    assert sink["queries"] == n_q


def test_tier_metrics_aggregates_via_registry(rng):
    table = make_table(rng, "uniform", N)
    qs = make_queries(rng, table, 512)
    n_q = len(qs)
    sidx = si.ShardedIndex.build("RMI", table, n_shards=4, b=64)
    si.reset_tier_metrics()
    si.sharded_lookup(sidx, qs, telemetry=True)
    si.sharded_lookup(sidx, qs, telemetry=True)
    m = si.tier_metrics()
    assert m["lookups"] == 2
    assert m["queries"] == 2 * n_q
    assert m["imbalance_peak"] >= m["imbalance_last"] > 0
    # and the same numbers are visible in a raw snapshot
    snap = obs.snapshot(prefix="route_")
    assert obs.sample_value(snap, "route_queries", tier="all") == 2 * n_q


def test_tuned_tier_metrics_render_from_snapshot(rng):
    from repro.index import RMISpec
    from repro.tune.rebuild import RebuildPolicy, TunedTier

    rng = np.random.default_rng(14)  # not the session stream: leaves other tests' tables alone
    table = make_table(rng, "uniform", N)
    qs = make_queries(rng, table, 256)
    tier = TunedTier(table, n_shards=2, policy=RebuildPolicy(), spec=RMISpec(b=64))
    tier.lookup(qs)
    m = tier.metrics()
    assert m["lookups"] == 1
    assert m["routing"]["queries"] == len(qs)
    # the per-tier labelset backs the proxy: poking it shows up in both
    tier.counters.pending += 7
    assert tier.counters.pending == 7
    assert obs.metric("tier_pending").value(tier=tier.name) == 7.0
    assert tier.metrics()["pending"] == 7


def test_engine_metrics_are_a_registry_snapshot():
    import jax

    from repro.configs import get as get_arch
    from repro.dist.sharding import single_device_ctx
    from repro.models import transformer
    from repro.serve.engine import DecodeEngine, Request

    spec = get_arch("qwen2-0.5b", reduced=True)
    cfg = spec.config
    params = transformer.init(jax.random.key(0), cfg)
    eng = DecodeEngine(params, cfg, single_device_ctx(), batch_slots=2, max_seq=64)
    prompt = np.arange(4, dtype=np.int32) % cfg.vocab
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=3))
    eng.run_until_drained(max_ticks=50)
    m = eng.metrics()
    assert m["requests_finished"] == 1
    assert m["tokens_decoded"] >= 2
    assert isinstance(m["index_trace_counts"], dict)
    snap = obs.snapshot(prefix="serve_")
    got = obs.sample_value(snap, "serve_requests_finished", engine=eng.name)
    assert got == m["requests_finished"]


def test_mutation_reports_feed_the_registry(rng):
    from repro.index import mutation

    table = make_table(rng, "uniform", N)
    idx = ix.build("GAPPED", table, leaf_cap=16, fill=0.5, delta_cap=64)
    before = obs.metric("mutation_requested").value(kind="GAPPED")
    keys = np.unique(make_queries(rng, table, 32))
    _idx2, report = mutation.insert_batch(idx, keys)
    assert report.requested == len(keys)
    after = obs.metric("mutation_requested").value(kind="GAPPED")
    assert after - before == len(keys)


# ---------------------------------------------------------------------------
# Harness smoke: SLO gates + CLI
# ---------------------------------------------------------------------------


def _slo_report(**over):
    metrics = {
        "slo/p50_us": 100.0,
        "slo/p99_us": 400.0,
        "slo/drop_rate": 0.0,
        "slo/exact": 1.0,
        "slo/cache_off/p50_us": 100.0,
        "slo/cache_off/p99_us": 400.0,
        "slo/cache/p50_us": 50.0,
        "slo/cache/p99_us": 200.0,
        "slo/cache/exact": 1.0,
        "slo/adv/drop_rate": 0.0,
        "slo/adv/retunes": 0.0,
        "slo/adv/hammer/exact": 1.0,
    }
    metrics.update(over)
    # drop a metric by passing <name>=None
    metrics = {k: v for k, v in metrics.items() if v is not None}
    return {"metrics": metrics, "slo": {"drop_rate_max": 0.01}}


def test_serve_slo_absolute_gates():
    sys.path.insert(0, str(ROOT))
    try:
        from benchmarks.serve_slo import check_slo
    finally:
        sys.path.pop(0)
    assert check_slo(_slo_report()) == []
    assert any("drop_rate" in f for f in check_slo(_slo_report(**{"slo/drop_rate": 0.5})))
    assert any("quantiles" in f for f in check_slo(_slo_report(**{"slo/p99_us": 1.0})))
    assert any("exact" in f for f in check_slo(_slo_report(**{"slo/exact": 0.0})))
    # PR 9 gates: adversarial drop rate, the retune-free invariant, the
    # cache leg's quantile sanity, and a leg dropped from the report
    assert any("drop_rate" in f for f in check_slo(_slo_report(**{"slo/adv/drop_rate": 0.5})))
    assert any("retunes" in f for f in check_slo(_slo_report(**{"slo/adv/retunes": 2.0})))
    assert any("quantiles" in f for f in check_slo(_slo_report(**{"slo/cache/p99_us": 1.0})))
    assert any("exact" in f for f in check_slo(_slo_report(**{"slo/adv/hammer/exact": 0.0})))
    assert any("missing" in f for f in check_slo(_slo_report(**{"slo/adv/retunes": None})))


def test_obs_cli_dump_and_diff(tmp_path):
    reg = fresh_registry()
    reg.metric("route_queries").inc(4, tier="a")
    reg.metric("lookup_latency_us").observe(5.0, **LATENCY_LABELS)
    before = tmp_path / "before.jsonl"
    before.write_text(obs.to_jsonl(reg.snapshot()))
    reg.metric("route_queries").inc(6, tier="a")
    after = tmp_path / "after.jsonl"
    after.write_text(obs.to_jsonl(reg.snapshot()))

    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    dump = subprocess.run(
        [sys.executable, "-m", "repro.obs", "dump", str(after)],
        capture_output=True, text=True, env=env, cwd=ROOT,
    )
    assert dump.returncode == 0, dump.stderr
    assert "route_queries" in dump.stdout and "lookup_latency_us" in dump.stdout
    d = subprocess.run(
        [sys.executable, "-m", "repro.obs", "diff", str(before), str(after)],
        capture_output=True, text=True, env=env, cwd=ROOT,
    )
    assert d.returncode == 0, d.stderr
    assert "route_queries" in d.stdout and "6" in d.stdout
