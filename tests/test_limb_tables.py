"""The tier's tables as resident u32 limb planes (``core.limbs.LimbTable``).

Answers against ``np.searchsorted`` on keys chosen to straddle the
high-limb boundaries, on every backend; every writer of a table row
(build, refresh, device refresh, rebalance, insert, save/load) leaves
the planes equal to the host split of its rows; no u64 copy of the
stack is left on the device; and the ``lookup_u64_table_traces``
counter tells the split path from the limb path.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import index as ix
from repro import obs
from repro.core import search
from repro.core.cdf import true_ranks
from repro.core.limbs import LimbTable
from repro.dist import sharded_index as si
from repro.index import registry
from repro.kernels.ops import split_u64
from repro.tune.device_fit import device_refresh

U32 = 1 << 32
KINDS = {
    "PGM_M": {"space_pct": 2.0, "a": 1.0},
    "SY-RMI": {"space_pct": 2.0, "ub": 0.04},
    "RS": {"eps": 16, "r_bits": 8},
}
# the last key sits 2^20 below the top of u64: the last shard's pad keys
# spread over that headroom, near the top of the range
_TOP = np.uint64(2**64 - 2**20)


def _limb_edge_table(seed: int = 7, n: int = 3000) -> np.ndarray:
    """Random keys plus runs that share a high limb with low limbs at 0,
    1, 2^31 and 2^32 - 1 (and their neighbours), so searches compare
    equal ``hi`` with ``lo`` on both sides of a query."""
    rng = np.random.default_rng(seed)
    his = np.asarray([0, 1, 5, 2**31 - 1, 2**31, 2**32 - 3, 2**32 - 2], dtype=np.uint64)
    los = np.asarray([0, 1, 2, 2**31 - 1, 2**31, U32 - 3, U32 - 2, U32 - 1], dtype=np.uint64)
    edge = ((his[:, None] << np.uint64(32)) | los[None, :]).ravel()
    filler = rng.integers(0, int(_TOP), n, dtype=np.uint64)
    return np.unique(np.concatenate([edge, filler, [_TOP]]))


def _edge_queries(table: np.ndarray, seed: int = 8) -> np.ndarray:
    """Every key, its neighbours, each high limb's first and last word,
    and the ends of u64."""
    rng = np.random.default_rng(seed)
    hi = np.unique(table >> np.uint64(32))
    first = hi << np.uint64(32)
    parts = [
        table,
        table - np.uint64(1),
        table + np.uint64(1),
        first,
        first - np.uint64(1),
        first | np.uint64(U32 - 1),
        rng.integers(0, 2**64 - 1, 256, dtype=np.uint64, endpoint=True),
        np.asarray([0, 1, 2**64 - 2, 2**64 - 1], dtype=np.uint64),
    ]
    return np.concatenate(parts).astype(np.uint64)


def _host_split(rows):
    rows = np.asarray(rows, dtype=np.uint64)
    return (rows >> np.uint64(32)).astype(np.uint32), (rows & np.uint64(U32 - 1)).astype(np.uint32)


def _assert_planes(tables: LimbTable, rows) -> None:
    """Both resident planes equal the host split of the u64 ``rows``."""
    hi, lo = _host_split(rows)
    assert tables.hi.dtype == jnp.uint32 and tables.lo.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(tables.hi), hi)
    np.testing.assert_array_equal(np.asarray(tables.lo), lo)


def _padded_rows(parts, m):
    return np.stack([si._pad_sorted_table(np.asarray(p, dtype=np.uint64), m) for p in parts])


@pytest.fixture(scope="module")
def edge_table():
    return _limb_edge_table()


# ---------------------------------------------------------------------------
# search: the limb compare against the u64 compare
# ---------------------------------------------------------------------------


def test_limb_compares_match_u64(edge_table):
    """``<=``, ``<`` and ``==`` on limbs equal the u64 compares on every
    pair of an edge key and an edge query."""
    keys = edge_table[:: max(1, len(edge_table) // 64)]
    qs = _edge_queries(keys)
    a, b = LimbTable.split(keys[:, None]), LimbTable.split(qs[None, :])
    kk, qq = keys[:, None], qs[None, :]
    np.testing.assert_array_equal(np.asarray(a <= b), kk <= qq)
    np.testing.assert_array_equal(np.asarray(a < b), kk < qq)
    np.testing.assert_array_equal(np.asarray(a == b), kk == qq)


@pytest.mark.parametrize(
    "keys",
    [
        np.uint64(2**64 - 1),
        np.asarray([0, U32 - 1, U32, 2**64 - 1], dtype=np.uint64),
        (np.arange(6, dtype=np.uint64).reshape(2, 3) << np.uint64(31)).T,  # not contiguous
        np.asarray([5, U32 + 7], dtype=">u8"),  # big-endian input
    ],
    ids=["scalar", "edges", "strided", "big-endian"],
)
def test_host_split_round_trips(keys):
    t = LimbTable.from_u64(keys)
    want = np.asarray(keys, dtype=np.uint64)
    assert t.shape == want.shape
    _assert_planes(t, want)
    np.testing.assert_array_equal(np.asarray(t), want)
    np.testing.assert_array_equal(np.asarray(t.combine()), want)


@pytest.mark.parametrize("procedure", ["bounded_bfs", "bounded_bbs_branchy"])
def test_bounded_searches_on_limbs(edge_table, procedure):
    """The bounded epilogues give the same ranks on a limb table as on
    the u64 table, over whole-table windows."""
    qs = _edge_queries(edge_table)
    n = len(edge_table)
    lo = jnp.zeros(qs.shape, jnp.int64)
    hi = jnp.full(qs.shape, n - 1, jnp.int64)
    fn = getattr(search, procedure)
    kw = {"max_window": n} if procedure == "bounded_bfs" else {}
    limbs = LimbTable.from_u64(edge_table)
    got = np.asarray(fn(limbs, jnp.asarray(qs), lo, hi, **kw))
    want = np.asarray(fn(jnp.asarray(edge_table), jnp.asarray(qs), lo, hi, **kw))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, np.searchsorted(edge_table, qs, side="right") - 1)


# ---------------------------------------------------------------------------
# the tier: parity on every backend, shape, residency
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_limb_tier_matches_searchsorted(edge_table, kind, backend):
    sidx = si.ShardedIndex.build(kind, edge_table, n_shards=4, **KINDS[kind])
    assert isinstance(sidx.tables, LimbTable)
    qs = _edge_queries(edge_table)
    if backend == "pallas":  # interpret mode: a slice of the edge queries
        qs = qs[:: max(1, len(qs) // 1024)]
    got = np.asarray(si.sharded_lookup(sidx, qs, mode="ref", backend=backend))
    np.testing.assert_array_equal(got, np.searchsorted(edge_table, qs, side="right") - 1)


def test_tables_shape_and_planes_after_build(edge_table):
    sidx = si.ShardedIndex.build("PGM_M", edge_table, n_shards=4, **KINDS["PGM_M"])
    m = int(sidx.info["m"])
    assert sidx.tables.shape == (4, m)
    assert sidx.tables.hi.shape == sidx.tables.lo.shape == (4, m)
    bounds = np.concatenate([[0], np.cumsum(np.asarray(sidx.counts))])
    parts = [edge_table[bounds[s] : bounds[s + 1]] for s in range(4)]
    rows = _padded_rows(parts, m)
    _assert_planes(sidx.tables, rows)
    # the host reads rows back as u64
    np.testing.assert_array_equal(np.asarray(sidx.tables), rows)
    assert np.asarray(sidx.tables[1]).dtype == np.uint64
    np.testing.assert_array_equal(np.asarray(sidx.tables[1][: len(parts[1])]), parts[1])
    # the last shard's pad climbs toward the top of u64
    assert rows[-1, -1] > _TOP
    # the Pallas tier path receives the resident planes as they are
    thi, tlo = split_u64(sidx.tables)
    assert thi is sidx.tables.hi and tlo is sidx.tables.lo


def test_build_leaves_no_u64_table_on_device():
    """Only the two u32 planes are placed: no live device array is a u64
    array as large as a shard row."""
    table = _limb_edge_table(seed=11, n=20_000)
    before = jax.live_arrays()  # held, so no id below is reused
    seen = {id(a) for a in before}
    sidx = si.ShardedIndex.build("PGM_M", table, n_shards=4, **KINDS["PGM_M"])
    jax.block_until_ready(sidx)
    m = int(sidx.tables.shape[1])
    new = [a for a in jax.live_arrays() if id(a) not in seen]
    assert [a.shape for a in new if a.dtype == jnp.uint64 and a.size >= m] == []
    assert sum(a.dtype == jnp.uint32 and a.shape == (4, m) for a in new) == 2


# ---------------------------------------------------------------------------
# every writer of a table row keeps the planes equal to the host split
# ---------------------------------------------------------------------------


def test_refresh_shard_writes_limb_row():
    rng = np.random.default_rng(42)
    table = np.unique(rng.integers(0, 2**63, 2048, dtype=np.uint64))
    sidx = si.ShardedIndex.build("BTREE", table, n_shards=4, fanout=8)
    m = int(sidx.tables.shape[1])
    counts = np.asarray(sidx.counts)
    parts = [np.asarray(sidx.tables[i])[: counts[i]] for i in range(4)]
    new_keys = parts[2][:-3]
    spec = registry.spec_for("BTREE", fanout=8)
    new_idx = registry.entry("BTREE").build(spec, si._pad_sorted_table(new_keys, m))
    s2 = si.refresh_shard(sidx, 2, new_idx, new_keys)
    parts[2] = new_keys
    _assert_planes(s2.tables, _padded_rows(parts, m))
    qs = _edge_queries(np.concatenate(parts))
    got = np.asarray(si.sharded_lookup(s2, qs))
    np.testing.assert_array_equal(got, true_ranks(np.concatenate(parts), qs))


@pytest.mark.parametrize("fit", ("fast", "scan"))
def test_device_refresh_writes_limb_row(fit):
    from repro.data import distributions

    table = distributions.generate("osm", 8000, seed=0)
    spec = ix.PGMSpec(eps=32)
    sidx = si.ShardedIndex.build(spec, table, n_shards=4)
    m = int(sidx.tables.shape[1])
    counts = np.asarray(sidx.counts)
    parts = [np.asarray(sidx.tables[i])[: counts[i]] for i in range(4)]
    rng = np.random.default_rng(1)
    drift = rng.integers(int(parts[1][10]), int(parts[1][-10]), 40, dtype=np.uint64)
    merged = np.union1d(parts[1], drift)
    s2, ok = device_refresh(sidx, 1, merged, eps=spec.eps, fit=fit)  # sidx donated
    if fit == "scan":  # the exact fit always installs at this headroom
        assert bool(ok)
    if bool(ok):
        parts[1] = merged
    _assert_planes(s2.tables, _padded_rows(parts, m))


def test_rebalance_shards_writes_limb_rows():
    rng = np.random.default_rng(5)
    table = np.unique(rng.integers(0, 2**63, 8704, dtype=np.uint64))
    sidx = si.ShardedIndex.build("RMI", table, n_shards=4, b=64)
    m = int(sidx.tables.shape[1])
    spec = registry.spec_for("RMI", b=64)
    build = registry.entry("RMI").build
    bounds = si.weighted_quantile_bounds(table, np.asarray(sidx.fences), [2.0, 1.0, 1.0, 1.0])
    s2 = si.rebalance_shards(sidx, table, bounds, lambda part: build(spec, part))
    parts = [table[bounds[s] : bounds[s + 1]] for s in range(4)]
    _assert_planes(s2.tables, _padded_rows(parts, m))


def test_insert_into_shard_leaves_planes():
    """GAPPED owns its keys: an insert leaves the build-time planes as
    they were."""
    rng = np.random.default_rng(3)
    table = np.unique(rng.integers(1, 2**62, size=3000, dtype=np.uint64))
    spec = ix.GappedSpec(leaf_cap=64, fill=0.75, delta_cap=128)
    sidx = si.ShardedIndex.build(spec, table, n_shards=4)
    before = _host_split(np.asarray(sidx.tables))
    fresh = np.setdiff1d(np.unique(rng.integers(1, 2**62, size=200, dtype=np.uint64)), table)
    owners = np.asarray(si.route_owners(sidx.fences, fresh))
    mine = fresh[owners == 1]
    sidx, _ = si.insert_into_shard(sidx, 1, mine)
    np.testing.assert_array_equal(np.asarray(sidx.tables.hi), before[0])
    np.testing.assert_array_equal(np.asarray(sidx.tables.lo), before[1])


def test_save_load_keeps_u64_file_and_limb_planes(edge_table, tmp_path):
    sidx = si.ShardedIndex.build("RS", edge_table, n_shards=4, **KINDS["RS"])
    path = os.path.join(tmp_path, "tier.npz")
    sidx.save(path)
    with np.load(path) as z:
        saved = z["tables"]
    assert saved.dtype == np.uint64 and saved.shape == sidx.tables.shape
    s2 = si.ShardedIndex.load(path)
    assert isinstance(s2.tables, LimbTable)
    _assert_planes(s2.tables, saved)
    qs = _edge_queries(edge_table)
    np.testing.assert_array_equal(
        np.asarray(si.sharded_lookup(s2, qs)), np.searchsorted(edge_table, qs, side="right") - 1
    )


# ---------------------------------------------------------------------------
# the lookup_u64_table_traces counter
# ---------------------------------------------------------------------------


def _u64_traces(program: str) -> float:
    snap = obs.snapshot(prefix="lookup_u64")
    return obs.sample_value(snap, "lookup_u64_table_traces", program=program)


def test_u64_table_trace_counter(edge_table):
    """The tier's label stays 0 across a traced tier lookup (its tables
    are limb planes); the index label rises by 1 on a fresh
    ``Index.lookup`` trace over a caller's u64 table, and not on a call
    that reuses that trace."""
    sidx = si.ShardedIndex.build("PGM_M", edge_table, n_shards=4, **KINDS["PGM_M"])
    before = ix.trace_counts().get(("sharded:PGM_M", "ref:xla"), 0)
    si.sharded_lookup(sidx, edge_table[:37], mode="ref")  # a batch size no other test traces
    assert ix.trace_counts()[("sharded:PGM_M", "ref:xla")] == before + 1
    assert _u64_traces("tier") == 0

    idx = ix.build("SY-RMI", edge_table, **KINDS["SY-RMI"])
    n0 = _u64_traces("index")
    idx.lookup(edge_table, edge_table[:39])
    assert _u64_traces("index") == n0 + 1
    got = idx.lookup(edge_table, edge_table[39:78])
    assert _u64_traces("index") == n0 + 1
    np.testing.assert_array_equal(np.asarray(got), np.arange(39, 78))
