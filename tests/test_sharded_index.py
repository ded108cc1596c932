"""Sharded multi-table lookup: stacking, routing, SPMD modes, refresh.

In-process tests cover the vmapped fallback path on whatever devices the
test process has, plus the shard_map a2a/allgather paths whenever the
process was started with enough (possibly forced) devices — the CI
``multihost`` leg sets ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
so these run on a real 4-way mesh there.  A subprocess test (the
``test_multidevice`` pattern) forces a 4-device CPU platform even when
the main process is single-device, so the collective paths are always
exercised by a plain local ``pytest`` run too.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import jax
import pytest

from repro import index as ix
from repro.core.cdf import true_ranks
from repro.dist import sharded_index as si
from repro.dist.sharding import ShardingCtx, make_mesh
from repro.index import registry

from conftest import make_table, make_queries

N = 2048
PARAMS_PER_KIND = {
    "L": {},
    "Q": {},
    "C": {},
    "KO": {"k": 7},
    "RMI": {"b": 64},
    "SY-RMI": {"space_pct": 2.0, "ub": 0.04},
    "PGM": {"eps": 32},
    "PGM_M": {"space_pct": 2.0, "a": 1.0},
    "RS": {"eps": 16, "r_bits": 8},
    "BTREE": {"fanout": 8},
}


def _table_and_queries(rng, n=N, nq=256):
    table = make_table(rng, "uniform", n)
    qs = make_queries(rng, table, nq)
    return table, qs


def _mesh_ctx(n_shards):
    """A mesh whose tp extent is ``n_shards``, or None if the process
    does not have enough devices."""
    if len(jax.devices()) < n_shards:
        return None
    mesh = make_mesh((1, n_shards), ("data", "model"))
    return ShardingCtx(mesh=mesh)  # tp_fsdp: tp -> model


# ---------------------------------------------------------------------------
# ShardingCtx.n / mesh_axes (the router reads both)
# ---------------------------------------------------------------------------


def test_sharding_ctx_n_resolved_product():
    """n() returns the resolved product over every mesh axis a logical
    axis occupies — including size-1-padded axes — and normalises
    string-valued rules instead of iterating their characters."""
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    ctx = ShardingCtx(mesh=mesh)
    assert ctx.mesh_axes("dp") == ("pod", "data")
    assert ctx.n("dp") == 1  # 1 * 1, both axes resolved
    assert ctx.n("tp") == 1

    # a bare-string rule must mean ONE mesh axis, not iter("model")
    ctx_s = ShardingCtx(mesh=mesh, rules={"tp": "model", "dp": ("pod", "data")})
    assert ctx_s.mesh_axes("tp") == ("model",)
    assert ctx_s.n("tp") == 1

    # unmapped -> 1; unknown mesh axis -> loud error, not silent 1
    assert ctx.n("nonexistent") == 1
    ctx_bad = ShardingCtx(mesh=mesh, rules={"tp": ("ghost",)})
    with pytest.raises(ValueError, match="ghost"):
        ctx_bad.n("tp")


def test_sharding_ctx_n_multidevice_extent():
    ctx = _mesh_ctx(len(jax.devices()))
    assert ctx is not None
    assert ctx.n("tp") == len(jax.devices())


# ---------------------------------------------------------------------------
# Build + stack + fallback lookup: bit-exact vs the concatenated table
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", list(PARAMS_PER_KIND))
def test_sharded_matches_concat_reference(rng, kind, backend):
    """Acceptance: sharded lookup == single-table Index.lookup on the
    concatenated table, for every registered kind."""
    table, qs = _table_and_queries(rng)
    want = true_ranks(table, qs)
    ref_idx = ix.build(kind, table, **PARAMS_PER_KIND[kind])
    ref = np.asarray(ref_idx.lookup(table, qs, backend=backend))
    np.testing.assert_array_equal(ref, want)
    for n_shards in (1, 2, 4):
        sidx = si.ShardedIndex.build(kind, table, n_shards=n_shards, **PARAMS_PER_KIND[kind])
        got = np.asarray(si.sharded_lookup(sidx, qs, backend=backend))
        np.testing.assert_array_equal(got, ref, err_msg=f"{kind}/{n_shards}-way/{backend}")


def test_routing_at_fence_keys(rng):
    """Exact fence keys route to the shard that starts with them;
    out-of-range queries resolve to -1 / n-1."""
    table, _ = _table_and_queries(rng)
    sidx = si.ShardedIndex.build("RMI", table, n_shards=4, b=64)
    fences = np.asarray(sidx.fences)
    owners = np.asarray(si.route_owners(sidx.fences, sidx.fences))
    np.testing.assert_array_equal(owners, np.arange(4))
    qs = np.concatenate(
        [
            fences,
            fences - 1,  # last key of the previous shard's range
            fences + 1,
            np.array([0, table.min(), table.max(), np.iinfo(np.uint64).max], np.uint64),
        ]
    ).astype(np.uint64)
    got = np.asarray(si.sharded_lookup(sidx, qs))
    np.testing.assert_array_equal(got, true_ranks(table, qs))
    assert got[len(fences)] == si.NO_PRED or fences[0] == 0  # below the global min


def test_predecessor_at_shard_boundaries(rng):
    """Predecessor semantics survive partitioning: for boundary keys the
    global rank is the last key of the *previous* shard for q just below
    a fence, and the fence key's own rank at the fence."""
    table, _ = _table_and_queries(rng)
    sidx = si.ShardedIndex.build("PGM", table, n_shards=4, eps=32)
    offsets = np.asarray(sidx.offsets)
    fences = np.asarray(sidx.fences)
    at = np.asarray(si.sharded_lookup(sidx, fences))
    np.testing.assert_array_equal(at, offsets)  # fence key ranks = shard offsets
    below = np.asarray(si.sharded_lookup(sidx, (fences[1:] - 1).astype(np.uint64)))
    np.testing.assert_array_equal(below, offsets[1:] - 1)  # predecessor in previous shard
    # and the plain Index.predecessor API agrees on the concatenated table
    ref_idx = ix.build("PGM", table, eps=32)
    np.testing.assert_array_equal(np.asarray(ref_idx.predecessor(table, fences)), offsets)


def test_stack_rejects_structural_mismatch(rng):
    table, _ = _table_and_queries(rng)
    a = ix.build("BTREE", table, fanout=8)
    b = ix.build("BTREE", table[:64], fanout=8)  # fewer levels
    with pytest.raises(ValueError, match="static"):
        si.stack_indexes([a, b])
    with pytest.raises(ValueError, match="kinds"):
        si.stack_indexes([a, ix.build("RMI", table, b=64)])


# ---------------------------------------------------------------------------
# save/load round-trip of the stacked tier
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["RMI", "PGM", "RS"])
def test_stacked_save_load_bit_exact(rng, kind, tmp_path):
    """npz of the stacked leaves stays bit-exact, and per-shard slices
    round-trip against a per-shard Index.save/load."""
    table, qs = _table_and_queries(rng)
    sidx = si.ShardedIndex.build(kind, table, n_shards=4, **PARAMS_PER_KIND[kind])
    path = os.path.join(tmp_path, f"{kind}-tier.npz")
    sidx.save(path)
    s2 = si.ShardedIndex.load(path)
    assert s2.kind == sidx.kind
    assert s2.index.static == sidx.index.static
    assert set(s2.index.arrays) == set(sidx.index.arrays)
    for k, v in sidx.index.arrays.items():
        a, b = np.asarray(v), np.asarray(s2.index.arrays[k])
        assert a.dtype == b.dtype, k
        np.testing.assert_array_equal(a, b, err_msg=k)
    for name in ("tables", "fences", "counts", "offsets"):
        np.testing.assert_array_equal(
            np.asarray(getattr(sidx, name)), np.asarray(getattr(s2, name)), err_msg=name
        )
    np.testing.assert_array_equal(
        np.asarray(si.sharded_lookup(s2, qs)), np.asarray(si.sharded_lookup(sidx, qs))
    )
    # a shard sliced out of the tier round-trips through Index.save/load
    shard = sidx.shard(2)
    spath = os.path.join(tmp_path, f"{kind}-shard2.npz")
    shard.save(spath)
    shard2 = ix.Index.load(spath)
    for k, v in shard.arrays.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(shard2.arrays[k]), err_msg=k)


# ---------------------------------------------------------------------------
# Donated refresh
# ---------------------------------------------------------------------------


def test_refresh_shard_swaps_rebuilt_shard():
    # own deterministic rng: the rebuilt shard must land in the same
    # bucketed-static tier regardless of which tests ran before
    rng = np.random.default_rng(42)
    table, qs = _table_and_queries(rng)
    sidx = si.ShardedIndex.build("BTREE", table, n_shards=4, fanout=8)
    m = int(sidx.tables.shape[1])
    counts = np.asarray(sidx.counts)
    shard_tables = [np.asarray(sidx.tables[i])[: counts[i]] for i in range(4)]
    # rebuild shard 2 with its last 3 keys retired (same padded length m,
    # so the B+-tree statics are identical by construction)
    new_keys = shard_tables[2][:-3]
    spec = registry.spec_for("BTREE", fanout=8)
    new_idx = registry.entry("BTREE").build(spec, si._pad_sorted_table(new_keys, m))
    s2 = si.refresh_shard(sidx, 2, new_idx, new_keys)
    new_table = np.concatenate([shard_tables[0], shard_tables[1], new_keys, shard_tables[3]])
    got = np.asarray(si.sharded_lookup(s2, qs))
    np.testing.assert_array_equal(got, true_ranks(new_table, qs))
    # offsets beyond the refreshed shard shifted down by the retired keys
    assert int(np.asarray(s2.offsets)[3]) == len(new_table) - len(shard_tables[3])
    assert int(np.asarray(s2.counts)[2]) == len(new_keys)


def test_refresh_shard_rejects_incompatible(rng):
    table, _ = _table_and_queries(rng)
    sidx = si.ShardedIndex.build("RMI", table, n_shards=2, b=64)
    other = ix.build("PGM", table, eps=32)
    with pytest.raises(ValueError, match="kind mismatch"):
        si.refresh_shard(sidx, 0, other, table[:10])


def test_refresh_shard_rejects_out_of_range_keys():
    """A rebuilt shard whose keys stray into a neighbour's fence slot is
    refused — it would silently corrupt every later shard's ranks."""
    rng = np.random.default_rng(43)
    table, _ = _table_and_queries(rng)
    sidx = si.ShardedIndex.build("BTREE", table, n_shards=4, fanout=8)
    m = int(sidx.tables.shape[1])
    spec = registry.spec_for("BTREE", fanout=8)
    # shard 1 rebuilt with keys reaching back into shard 0's range
    bad_low = table[: int(sidx.counts[0]) + 4]
    idx_low = registry.entry("BTREE").build(spec, si._pad_sorted_table(bad_low[:m], m))
    with pytest.raises(ValueError, match="previous"):
        si.refresh_shard(sidx, 1, idx_low, bad_low[:m])
    # shard 1 rebuilt with its key window shifted into the next fence slot
    hi_start = int(sidx.offsets[1]) + 4
    bad_hi = table[hi_start : hi_start + int(sidx.counts[1])]
    idx_hi = registry.entry("BTREE").build(spec, si._pad_sorted_table(bad_hi, m))
    with pytest.raises(ValueError, match="next"):
        si.refresh_shard(sidx, 1, idx_hi, bad_hi)


def test_sharded_lookup_rejects_unknown_backend(rng):
    table, qs = _table_and_queries(rng, n=256, nq=16)
    sidx = si.ShardedIndex.build("RMI", table, n_shards=2, b=64)
    with pytest.raises(ValueError, match="tier backend"):
        si.sharded_lookup(sidx, qs, backend="xIa")
    # pallas is a first-class tier backend (batched fused kernels)
    assert "pallas" in si.TIER_BACKENDS
    got = np.asarray(si.sharded_lookup(sidx, qs, backend="pallas"))
    np.testing.assert_array_equal(got, true_ranks(table, qs))


# ---------------------------------------------------------------------------
# shard_map paths in-process (needs >= 4 devices, e.g. the multihost leg)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["a2a", "allgather"])
@pytest.mark.parametrize("n_shards", [2, 4])
def test_spmd_modes_match_reference(rng, n_shards, mode, backend):
    ctx = _mesh_ctx(n_shards)
    if ctx is None:
        pytest.skip(f"needs {n_shards} devices (multihost CI leg / subprocess test)")
    table, qs = _table_and_queries(rng)
    want = true_ranks(table, qs)
    for kind in ("RMI", "PGM"):
        sidx = si.ShardedIndex.build(kind, table, n_shards=n_shards, **PARAMS_PER_KIND[kind])
        got = np.asarray(
            si.sharded_lookup(
                sidx, qs, ctx, mode=mode, backend=backend, cap_factor=float(n_shards)
            )
        )
        np.testing.assert_array_equal(got, want, err_msg=f"{kind}/{mode}/{n_shards}")


def test_a2a_capacity_overflow_reports_dropped(rng):
    ctx = _mesh_ctx(4)
    if ctx is None:
        pytest.skip("needs 4 devices (multihost CI leg / subprocess test)")
    table, _ = _table_and_queries(rng)
    sidx = si.ShardedIndex.build("RMI", table, n_shards=4, b=64)
    skew = np.full(64, table[-1], dtype=np.uint64)  # all owned by the last shard
    got = np.asarray(si.sharded_lookup(sidx, skew, ctx, mode="a2a", cap_factor=0.26))
    n = len(table)
    assert np.all((got == si.DROPPED) | (got == n - 1))
    assert np.any(got == si.DROPPED)  # dropped, never silently mis-answered
    exact = np.asarray(si.sharded_lookup(sidx, skew, ctx, mode="a2a", cap_factor=4.0))
    np.testing.assert_array_equal(exact, np.full(64, n - 1))


# ---------------------------------------------------------------------------
# Forced 4-device subprocess: collective paths without relying on the
# parent process's device count (the test_multidevice pattern).
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
import repro
from repro import index as ix
from repro.core import as_table
from repro.core.cdf import true_ranks
from repro.dist import sharded_index as si
from repro.dist.sharding import ShardingCtx, make_mesh

assert len(jax.devices()) == 4
rng = np.random.default_rng(5)
table = as_table(rng.integers(0, 2**63, size=2500, dtype=np.uint64))
qs = np.concatenate([
    rng.choice(table, 200),
    rng.integers(0, 2**63, 100, dtype=np.uint64),
    np.array([0, table.min(), table.max(), 2**64 - 1], dtype=np.uint64),
]).astype(np.uint64)
want = true_ranks(table, qs)

for n_shards, mesh_shape in ((2, (2, 2)), (4, (1, 4))):
    mesh = make_mesh(mesh_shape, ("data", "model"))
    ctx = ShardingCtx(mesh=mesh, rules={"tp": ("model",) if n_shards != 4 else ("data", "model")})
    assert ctx.n("tp") == n_shards, (ctx.n("tp"), n_shards)
    for kind, params in [("RMI", dict(b=64)), ("PGM", dict(eps=32)), ("BTREE", dict(fanout=8))]:
        sidx = si.ShardedIndex.build(kind, table, n_shards=n_shards, **params)
        for mode in ("a2a", "allgather"):
            got = np.asarray(si.sharded_lookup(
                sidx, qs, ctx, mode=mode, cap_factor=float(n_shards)))
            assert np.array_equal(got, want), (kind, n_shards, mode)
    # place(): each tp device holds its own shard row; answers unchanged
    placed = sidx.place(ctx)
    for plane in (placed.tables.hi, placed.tables.lo):  # the table's two u32 limb planes
        rows = {s.device: s.data.shape for s in plane.addressable_shards}
        assert len(rows) == 4 and all(r[0] == 1 for r in rows.values()), rows
    got = np.asarray(si.sharded_lookup(placed, qs, ctx, mode="a2a", cap_factor=float(n_shards)))
    assert np.array_equal(got, want), ("placed", n_shards)
    print(f"OK {n_shards}-way a2a+allgather")

# donated refresh under the 4-way mesh: swap shard 1, results track the new tier
from repro.index import registry
mesh = make_mesh((1, 4), ("data", "model"))
ctx = ShardingCtx(mesh=mesh)
sidx = si.ShardedIndex.build("RMI", table, n_shards=4, b=64)
m = int(sidx.tables.shape[1])
counts = np.asarray(sidx.counts)
shard_tables = [np.asarray(sidx.tables[i])[: counts[i]] for i in range(4)]
new_keys = shard_tables[1][:-5]
spec = registry.spec_for("RMI", b=64)
new_idx = registry.entry("RMI").build(spec, si._pad_sorted_table(new_keys, m))
s2 = si.refresh_shard(sidx, 1, new_idx, new_keys)
new_table = np.concatenate([shard_tables[0], new_keys, shard_tables[2], shard_tables[3]])
got = np.asarray(si.sharded_lookup(s2, qs, ctx, mode="a2a", cap_factor=4.0))
assert np.array_equal(got, true_ranks(new_table, qs))
print("OK donated refresh under mesh")

# LearnedKeyedEmbedding id-translation through the sharded tier
from repro.models.embedding import LearnedKeyedEmbedding
raw = rng.integers(0, 2**63, size=800, dtype=np.uint64)
lke = LearnedKeyedEmbedding.build(raw, dim=8, seed=3, ctx=ctx, n_shards=4)
probe = np.concatenate([raw[:16], rng.integers(0, 2**63, 8, dtype=np.uint64)])
vecs_sharded = np.asarray(lke.lookup(probe))
lke1 = LearnedKeyedEmbedding.build(raw, dim=8, seed=3)
np.testing.assert_allclose(vecs_sharded, np.asarray(lke1.lookup(probe)))
print("OK LearnedKeyedEmbedding sharded id-translation")
print("ALL SHARDED OK")
"""


@pytest.mark.slow
def test_sharded_collectives_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"  # fake CPU devices by design; never the chip
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True, timeout=1200
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr[-4000:]}"
    assert "ALL SHARDED OK" in res.stdout


# ---------------------------------------------------------------------------
# Skew-aware rebalancing (PR 9): weighted-quantile fences + donated re-shard
# ---------------------------------------------------------------------------


def test_weighted_quantile_bounds_degenerate_skew():
    """All observed traffic on one shard: the split must hand that
    shard's keys out across every shard while staying a strictly
    increasing >= 1-key partition; all-zero weights fall back even."""
    rng = np.random.default_rng(51)
    table, _ = _table_and_queries(rng, n=4096)
    sidx = si.ShardedIndex.build("RMI", table, n_shards=4, b=64)
    fences = np.asarray(sidx.fences)
    bounds = si.weighted_quantile_bounds(table, fences, [1.0, 0.0, 0.0, 0.0])
    assert bounds[0] == 0 and bounds[-1] == len(table)
    assert (np.diff(bounds) >= 1).all()
    # the hot shard's old key range (first quarter) is split across all
    # shards: every inner bound lands inside it
    assert (bounds[1:-1] <= len(table) // 4).all()
    even = si.weighted_quantile_bounds(table, fences, [0.0, 0.0, 0.0, 0.0])
    np.testing.assert_array_equal(even, [0, 1024, 2048, 3072, 4096])
    # single-key-per-shard degenerate table still partitions
    tiny = table[:4]
    tb = si.weighted_quantile_bounds(tiny, tiny, [9.0, 0.0, 0.0, 0.0])
    np.testing.assert_array_equal(tb, [0, 1, 2, 3, 4])


def test_rebalance_shards_donated_path_exact(rng):
    """Moderate skew on a tier with stacked-capacity slack: the pure
    donated re-shard path (no restack) must produce bit-exact lookups
    with zero drops, and move the fences to the new bounds."""
    # 4 x 2176 resident keys, m = pow2ceil(2176) = 4096: every shard has
    # slack, so moderate boundary moves install via refresh_shard alone
    table, qs = _table_and_queries(rng, n=8704, nq=512)
    sidx = si.ShardedIndex.build("RMI", table, n_shards=4, b=64)
    spec = registry.spec_for("RMI", b=64)
    build = registry.entry("RMI").build
    bounds = si.weighted_quantile_bounds(
        table, np.asarray(sidx.fences), [2.0, 1.0, 1.0, 1.0]
    )
    assert not np.array_equal(np.diff(bounds), np.asarray(sidx.counts))
    s2 = si.rebalance_shards(sidx, table, bounds, lambda part: build(spec, part))
    np.testing.assert_array_equal(np.asarray(s2.counts), np.diff(bounds))
    np.testing.assert_array_equal(np.asarray(s2.fences), table[bounds[:-1]])
    got = np.asarray(si.sharded_lookup(s2, qs))
    assert (got != si.DROPPED).all()
    np.testing.assert_array_equal(got, true_ranks(table, qs))


def test_rebalance_boundary_fence_keys(rng):
    """Queries exactly ON and adjacent to the rebalanced fences — the
    routing seam a off-by-one in the new bounds would corrupt first."""
    table, _ = _table_and_queries(rng, n=8704)
    sidx = si.ShardedIndex.build("RMI", table, n_shards=4, b=64)
    spec = registry.spec_for("RMI", b=64)
    build = registry.entry("RMI").build
    bounds = si.weighted_quantile_bounds(
        table, np.asarray(sidx.fences), [3.0, 1.0, 2.0, 1.0]
    )
    s2 = si.rebalance_shards(sidx, table, bounds, lambda part: build(spec, part))
    fence_keys = table[bounds[1:-1]]
    qs = np.concatenate(
        [fence_keys, fence_keys - np.uint64(1), fence_keys + np.uint64(1), table[:1]]
    )
    got = np.asarray(si.sharded_lookup(s2, qs, mode="ref"))
    np.testing.assert_array_equal(got, true_ranks(table, qs))


def test_tier_rebalance_with_populated_gapped_delta():
    """Rebalancing a GAPPED tier whose delta buffers hold live overflow
    keys: the re-shard must fold delta + leaves into the new partition
    with zero key loss and exact answers."""
    from repro.index import GappedSpec
    from repro.tune import RebuildPolicy, TunedTier

    rng = np.random.default_rng(57)
    table = np.unique(rng.integers(1, 2**61, size=3000, dtype=np.uint64))
    tier = TunedTier(
        table,
        n_shards=4,
        policy=RebuildPolicy(retune_frac=10.0),
        spec=GappedSpec(leaf_cap=64, fill=0.75, delta_cap=512),
    )
    # a dense cluster inside one leaf's key range exhausts its gaps and
    # overflows into the shard's sorted delta
    lo, hi = int(table[40]), int(table[41])
    cluster = np.unique(
        rng.integers(lo + 1, max(hi, lo + 2), size=120, dtype=np.uint64)
    )
    cluster = np.setdiff1d(cluster, table)
    tier.insert_batch(cluster)
    merged = np.union1d(table, cluster)
    assert tier.counters.overflowed > 0, "cluster failed to reach the delta buffer"
    delta_live = int(np.asarray(tier.sidx.index.arrays["delta_count"]).sum())
    assert delta_live > 0
    tier.rebalance(weights=np.array([6.0, 1.0, 1.0, 1.0]))
    np.testing.assert_array_equal(tier._merged_table(), merged)
    qs = np.concatenate([rng.choice(merged, 256), cluster[:32]])
    got = np.asarray(tier.lookup(qs, mode="ref"))
    np.testing.assert_array_equal(got, true_ranks(merged, qs))
    assert tier.metrics()["rebalances"] >= 1
    assert tier.metrics()["retunes"] == 0


def test_tier_refresh_non_pow2_shard_regression():
    """Regression: a refreshed shard whose resident count is not a power
    of two must be FITTED on the padded capacity-m table.  The seed
    built the replacement on the raw merged keys, so static-kind models
    (which normalise predictions by lookup-time table length)
    mispredicted against the stacked padded row the moment pad > 0."""
    from repro.tune import RebuildPolicy, TunedTier

    rng = np.random.default_rng(59)
    # 500 keys/shard, m = 512: pad > 0, the seed-corrupting shape
    table = np.unique(rng.integers(1, 2**61, size=1100, dtype=np.uint64))[:1000]
    tier = TunedTier(
        table,
        n_shards=2,
        policy=RebuildPolicy(retune_frac=10.0, shard_refresh_frac=10.0),
        spec=ix.RMISpec(b=32),
    )
    assert int(tier.sidx.counts[0]) < int(tier.sidx.tables.shape[1])
    for s in range(2):
        tier.refresh(s)  # identity refresh: no pending keys land
    assert tier.counters.forced_restacks == 0
    qs = rng.choice(table, size=512).astype(np.uint64)
    np.testing.assert_array_equal(
        np.asarray(tier.lookup(qs, mode="ref")), true_ranks(table, qs)
    )


def test_tier_maybe_rebalance_windowed_trigger(rng):
    """The drift window: sustained single-shard hammering must trip the
    query-driven rebalance (and only after ``rebalance_min_lookups``),
    serving every batch exactly throughout."""
    from repro.dist import reset_tier_metrics
    from repro.tune import RebuildPolicy, TunedTier

    reset_tier_metrics()
    table, _ = _table_and_queries(rng, n=8704)
    tier = TunedTier(
        table,
        n_shards=4,
        policy=RebuildPolicy(
            retune_frac=10.0,
            rebalance_imbalance=1.5,
            rebalance_min_lookups=3,
        ),
        spec=ix.RMISpec(b=64),
    )
    hot = table[: len(table) // 4]  # every query owned by shard 0
    for _ in range(8):
        qs = rng.choice(hot, size=256).astype(np.uint64)
        got = np.asarray(tier.lookup(qs, mode="ref"))
        np.testing.assert_array_equal(got, true_ranks(table, qs))
    m = tier.metrics()
    assert m["rebalances"] >= 1, "sustained skew never tripped the rebalancer"
    assert m["retunes"] == 0
    # post-rebalance: shard 0 no longer owns the whole hot range
    assert int(np.asarray(tier.sidx.counts)[0]) < len(hot)
