"""The bytes a lookup needs: hand counts on hand-made models, and a
query-by-query count on real SY-RMI, PGM_M and 4-shard tier builds."""

from __future__ import annotations

import math

import numpy as np
import pytest

from benchmarks.chip import data, needed_bytes


def rmi_arrays(leaf_r, leaf_eps):
    b = len(leaf_eps)
    return {
        "root_coef": np.zeros(4), "kmin": np.float64(0), "inv_span": np.float64(1),
        "leaf_slope": np.zeros(b), "leaf_icept": np.zeros(b),
        "leaf_eps": np.asarray(leaf_eps, np.int64), "leaf_r": np.asarray(leaf_r, np.int64),
    }


def test_rmi_hand_count():
    # leaf 0 holds ranks 0..3 (window [0, 4], w = min(2*1+2, 5) = 4: 2 reads),
    # leaf 1 ranks 4..9 (window [3, 9], w = min(2*3+2, 7) = 7: 3 reads);
    # model: 4 root coefficients + kmin + inv_span + slope, icept, eps + 2 fences
    a = rmi_arrays([0, 4, 10], [1, 3])
    got = needed_bytes.rmi(a, 10, np.array([0, 3, 4, 9, -1]))
    model = 32 + 8 + 8 + 24 + 16
    assert got.tolist() == [16 + model + 16] * 2 + [16 + model + 24] * 2 + [16 + model + 16]


def pgm_arrays():
    # root: 1 segment over 3 leaf segments; leaf segments over table rows
    # [0, 4), [4, 7), [7, 20); eps 2 caps every window at 2*(2+1)+2 = 8
    return {
        "keys": np.zeros(4, np.uint64), "slope": np.zeros(4),
        "rank0": np.array([0, 3, 0, 4, 7, 20], np.int64),
        "off": np.array([0, 1, 4]), "off_r": np.array([0, 2, 6]),
        "sizes": np.array([1, 3]), "eps": np.int64(2),
    }


def test_pgm_hand_count():
    got = needed_bytes.pgm(pgm_arrays(), 20, np.array([5, 15, -1]))
    # per level: key, slope and two rank fences (32 B) for each of 2 levels
    # rank 5: leaf seg 1 window [3, 6] (w 4: 2 reads), root window [0, 2] (w 3: 2 reads)
    # rank 15: leaf seg 2 window [6, 19] (w 8: 3 reads), root 2 reads
    # rank -1: leaf seg 0 window [0, 3] (w 4: 2 reads), root 2 reads
    assert got.tolist() == [16 + 64 + 16 + 16, 16 + 64 + 24 + 16, 16 + 64 + 16 + 16]


def test_tier_hand_count():
    a = rmi_arrays([0, 4, 10], [1, 3])
    got = needed_bytes.tier_lookup("SY-RMI", [a, a], 10, np.array([0, 8]), np.array([3, 8, 9]))
    # the owner shard's lookup on its local rank (3, 0, 1: all in leaf 0)
    # + one boundary fence + the owner's count and offset
    assert got.tolist() == [120 + 8 + 16] * 3


def loop_rmi(a, n, j):
    r, eps = a["leaf_r"], a["leaf_eps"]
    b = len(eps)
    leaf = max(l for l in range(b) if r[l] <= max(j, 0))
    lo, hi = max(r[leaf] - 1, 0), min(r[leaf + 1], n - 1)
    w = min(2 * eps[leaf] + 2, hi - lo + 1)
    model = sum(a[k].nbytes for k in ("root_coef", "kmin", "inv_span")) + 8 * 3 + 16
    return 16 + model + 8 * max(0, math.ceil(math.log2(w)))


def loop_pgm(a, n, j):
    sizes, off_r, eps = a["sizes"], a["off_r"], int(a["eps"])
    total, below = 16, max(j, 0)
    for lvl in reversed(range(len(sizes))):
        r0 = a["rank0"][off_r[lvl]: off_r[lvl] + sizes[lvl] + 1]
        seg = max(s for s in range(sizes[lvl]) if r0[s] <= below)
        lo, hi = max(r0[seg] - 1, 0), r0[seg + 1] - 1
        if lvl == len(sizes) - 1:
            hi = min(hi, n - 1)
        w = min(2 * (eps + 1) + 2, hi - lo + 1)
        total += 32 + 8 * max(0, math.ceil(math.log2(w)))
        below = seg
    return total


@pytest.fixture(scope="module")
def small():
    t = data.table("osm", 30_000, 5)
    rng = np.random.default_rng(0)
    ranks = np.concatenate([rng.integers(0, len(t), 300), [-1, 0, len(t) - 1]])
    return t, ranks


@pytest.mark.parametrize("kind,loop", [("SY-RMI", loop_rmi), ("PGM_M", loop_pgm)])
def test_real_index_matches_a_query_by_query_count(small, kind, loop):
    from repro import index as ix

    t, ranks = small
    idx = ix.build(kind, t, space_pct=0.05)
    a = {k: np.asarray(v) for k, v in idx.arrays.items()}
    got = needed_bytes.index_lookup(kind, idx.arrays, len(t), ranks)
    assert got.tolist() == [loop(a, len(t), int(j)) for j in ranks]


def test_real_tier_matches_a_query_by_query_count(small):
    from repro.dist.sharded_index import ShardedIndex

    t, ranks = small
    s = ShardedIndex.build("PGM_M", t, n_shards=4, space_pct=0.05)
    shards = [{k: np.asarray(v) for k, v in s.shard(i).arrays.items()} for i in range(4)]
    offsets = np.asarray(s.offsets)
    rows = int(s.tables.shape[1])
    got = needed_bytes.tier_lookup("PGM_M", shards, rows, offsets, ranks)
    want = []
    for j in ranks:
        owner = max(i for i in range(4) if offsets[i] <= max(j, 0))
        want.append(loop_pgm(shards[owner], rows, int(j - offsets[owner])) + 8 * 3 + 16)
    assert got.tolist() == want
    with pytest.raises(KeyError):
        needed_bytes.index_lookup("RS", shards[0], rows, ranks)
