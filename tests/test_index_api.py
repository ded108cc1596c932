"""Unified Index API: registry completeness, pytree round-trips, npz
save/load, backend parity, and the shared-jit trace-count guarantee.

These are the acceptance tests of the api_redesign PR: an index is a
pytree of flat arrays driven by ONE jitted lookup per kind — not a
Python object closed over by a fresh ``jax.jit`` per model.
"""

import os

import numpy as np
import jax
import pytest

from repro import index as ix
from repro.core.cdf import true_ranks
from repro.index.impls import query_impl
from repro.data import distributions

from conftest import make_table, make_queries

# one cheap spec per registered kind (covers the whole registry)
SPEC_PER_KIND = {
    "L": ix.AtomicSpec(degree=1),
    "Q": ix.AtomicSpec(degree=2),
    "C": ix.AtomicSpec(degree=3),
    "KO": ix.KOSpec(k=7),
    "RMI": ix.RMISpec(b=64, root_type="linear"),
    "SY-RMI": ix.SYRMISpec(space_pct=2.0, ub=0.04),
    "PGM": ix.PGMSpec(eps=32),
    "PGM_M": ix.PGMBicriteriaSpec(space_pct=2.0, a=1.0),
    "RS": ix.RSSpec(eps=16, r_bits=8),
    "BTREE": ix.BTreeSpec(fanout=8),
    "GAPPED": ix.GappedSpec(leaf_cap=64, fill=0.75, delta_cap=256),
}


def _tables(rng, n=4000):
    uniform = make_table(rng, "uniform", n)
    osm = np.unique(distributions.generate("osm", n, seed=11))
    return {"uniform": uniform, "osm": osm}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_registry_completeness():
    """Every paper kind is registered, in the paper's order, plus the
    updatable GAPPED kind appended by the mutation-API redesign."""
    assert ix.kinds() == (
        "L", "Q", "C", "KO", "RMI", "SY-RMI", "PGM", "PGM_M", "RS", "BTREE", "GAPPED",
    )
    assert set(SPEC_PER_KIND) == set(ix.kinds())
    for kind in ix.kinds():
        e = ix.entry(kind)
        assert e.kind == kind
        assert callable(e.build)
        # loose-params shim constructs the right spec class
        assert isinstance(ix.spec_for(kind), e.spec_cls)


def test_registry_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown index kind"):
        ix.entry("ZZTREE")


def test_spec_hashable_and_named():
    seen = {s for s in SPEC_PER_KIND.values()}  # hashable
    assert len(seen) == len(SPEC_PER_KIND)
    assert ix.RMISpec(b=64).display_name() == "RMI[b=64,root_type=linear]"
    assert ix.AtomicSpec(degree=2).kind == "Q"


# ---------------------------------------------------------------------------
# Pytree round-trip under jit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", list(SPEC_PER_KIND))
def test_pytree_roundtrip_under_jit(rng, kind):
    table = _tables(rng)["uniform"]
    idx = ix.build(SPEC_PER_KIND[kind], table)

    leaves, treedef = jax.tree_util.tree_flatten(idx)
    assert all(hasattr(l, "dtype") for l in leaves), "leaves must be arrays"
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.kind == idx.kind and rebuilt.static == idx.static

    through = jax.jit(lambda i: i)(idx)  # Index passes through jit boundaries
    assert through.kind == idx.kind and through.static == idx.static
    for k in idx.arrays:
        np.testing.assert_array_equal(np.asarray(through.arrays[k]), np.asarray(idx.arrays[k]))
    # and it still answers queries exactly
    qs = make_queries(rng, table, 100)
    got = np.asarray(through.lookup(table, qs))
    np.testing.assert_array_equal(got, true_ranks(table, qs))


# ---------------------------------------------------------------------------
# save / load
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", list(SPEC_PER_KIND))
def test_save_load_bit_exact(rng, kind, tmp_path):
    """Acceptance: Index.save/load round-trips every registered kind."""
    table = _tables(rng)["osm"]
    idx = ix.build(SPEC_PER_KIND[kind], table)
    path = os.path.join(tmp_path, f"{kind}.npz")
    idx.save(path)
    idx2 = ix.Index.load(path)
    assert idx2.kind == idx.kind
    assert idx2.static == idx.static
    assert set(idx2.arrays) == set(idx.arrays)
    for k, v in idx.arrays.items():
        a, b = np.asarray(v), np.asarray(idx2.arrays[k])
        assert a.dtype == b.dtype, k
        np.testing.assert_array_equal(a, b, err_msg=k)
    assert idx2.space_bytes() == idx.space_bytes()
    qs = make_queries(rng, table, 100)
    np.testing.assert_array_equal(
        np.asarray(idx2.lookup(table, qs)), np.asarray(idx.lookup(table, qs))
    )


# ---------------------------------------------------------------------------
# Backend parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("table_kind", ["uniform", "osm"])
@pytest.mark.parametrize("kind", list(SPEC_PER_KIND))
def test_backend_parity(rng, kind, table_kind, backend):
    """xla == ref == bbs == pallas (interpret mode) on every kind.

    ``backend`` comes from the conftest fixture driven by
    ``REPRO_TEST_BACKENDS`` — one CI matrix leg per backend."""
    table = _tables(rng)[table_kind]
    qs = make_queries(rng, table, 200)
    want = true_ranks(table, qs)
    idx = ix.build(SPEC_PER_KIND[kind], table)
    if backend not in idx.backends():
        # honest claims: an unimplemented backend is a loud error, not a
        # silent fallback (GAPPED has no pallas path yet)
        with pytest.raises(ValueError, match="supports backends"):
            idx.lookup(table, qs, backend=backend)
        return
    got = np.asarray(idx.lookup(table, qs, backend=backend))
    np.testing.assert_array_equal(got, want, err_msg=f"{kind}/{backend}")


# ---------------------------------------------------------------------------
# Shared jitted lookup: trace counts
# ---------------------------------------------------------------------------


def test_single_trace_per_kind_across_instances(rng):
    """The headline of the redesign: N same-structure models of a kind
    share exactly ONE trace of the shared lookup (the old API paid one
    ``jax.jit`` closure trace per model)."""
    n = 4096
    tables = [make_table(np.random.default_rng(s), "uniform", n) for s in (1, 2, 3)]
    tables = [t[:4000] for t in tables]  # identical shapes across instances
    qs = tables[0][:256].astype(np.uint64)

    ix.reset_trace_counts()
    for t in tables:
        idx = ix.build(ix.RMISpec(b=64), t)
        idx.lookup(t, qs)
    counts = ix.trace_counts()
    assert counts == {("RMI", "xla"): 1}, counts

    # a different kind gets its own (single) trace; same kind again: none
    ix.reset_trace_counts()
    for t in tables:
        ix.build(ix.BTreeSpec(fanout=8), t).lookup(t, qs)
        ix.build(ix.RMISpec(b=64), t).lookup(t, qs)
    counts = ix.trace_counts()
    assert counts.get(("BTREE", "xla")) == 1, counts
    assert counts.get(("RMI", "xla"), 0) == 0, counts  # cache survived the reset window


def test_parametric_budget_sweep_traces_bounded(rng):
    """The query_parametric scenario: a sweep of SY-RMI space budgets
    over several same-tier tables compiles once per distinct budget
    (array structure), not once per model — 6 models, <= 3 traces."""
    n = 4000
    t1 = make_table(np.random.default_rng(7), "uniform", 4300)[:n]
    t2 = make_table(np.random.default_rng(8), "uniform", 4300)[:n]
    qs = t1[:256].astype(np.uint64)

    ix.reset_trace_counts()
    n_models = 0
    for t in (t1, t2):
        for pct in (0.5, 1.0, 2.0):
            ix.build(ix.SYRMISpec(space_pct=pct, ub=0.04), t).lookup(t, qs)
            n_models += 1
    counts = ix.trace_counts()
    assert n_models == 6
    assert sum(counts.values()) <= 3, counts


def test_info_metadata_passthrough(rng):
    """Build metadata (name, eps, ...) rides on the host-side Index but
    never enters the pytree (so it cannot fragment jit caches)."""
    table = _tables(rng)["uniform"]
    idx = ix.build(ix.PGMSpec(eps=32), table)
    assert idx.eps == 32
    assert idx.n_segments_l0 >= 1
    assert idx.name.startswith("PGM")
    _, treedef = jax.tree_util.tree_flatten(idx)
    idx2 = jax.tree_util.tree_unflatten(treedef, jax.tree_util.tree_flatten(idx)[0])
    assert idx2.info == {}  # metadata intentionally dropped


# ---------------------------------------------------------------------------
# Named scopes: the device trace tells model predict from bounded search
# ---------------------------------------------------------------------------

INTERVAL_KINDS = [k for k in SPEC_PER_KIND if query_impl(k).lookup is None]


@pytest.mark.parametrize("kind", INTERVAL_KINDS)
def test_lookup_ops_carry_predict_and_search_scopes(kind):
    """Every kind that answers through ``intervals`` + bounded search
    lowers its predict ops under ``predict/`` and its search ops under
    ``search/``, inside the one ``_lookup_jit`` program."""
    table = make_table(np.random.default_rng(21), "uniform", 4000)
    idx = ix.build(SPEC_PER_KIND[kind], table)
    lowered = ix.index._lookup_jit.lower(idx, table, table[:64], backend="xla")
    text = lowered.as_text(debug_info=True)
    assert "jit(_lookup_jit)/predict/" in text, kind
    assert "jit(_lookup_jit)/search/" in text, kind
