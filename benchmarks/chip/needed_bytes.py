"""HBM bytes that one lookup needs, counted from the built model and the
batch, whatever backend runs it.  The lookup rooflines divide these by
the chip's HBM bandwidth: every read is an 8-byte gather that depends on
the one before it, so bandwidth, not arithmetic, is the bound.

Per query:

* the query key and its answer: 8 + 8 bytes;
* the model entries one prediction reads (the root or the route, then
  one leaf or one segment per level);
* 8 bytes x ceil(log2 w) reads of a sorted array, for each window of
  ``w`` candidates that the built model states for the query's leaf or
  segment (the table for the last level; the next level's keys above it).

``w`` is the model's stated window, clipped to the leaf's or segment's
rank range as the model clips it, and not the program's bucketed trip
count: a change that narrows windows or searches fewer shards shows as a
higher share, and one that re-implements the same work does not.  The
leaf or segment of a query is the one whose rank range holds its exact
predecessor rank (the reference's answer).
"""

from __future__ import annotations

import numpy as np

QUERY_BYTES = 16  # the key in, the rank out


def ceil_log2(w: np.ndarray) -> np.ndarray:
    """ceil(log2 w) for integer w >= 1, 0 for w <= 1 (exact in integers)."""
    w = np.maximum(np.asarray(w, dtype=np.int64), 1)
    return np.ceil(np.log2(w.astype(np.float64)) - 1e-12).astype(np.int64)


def _host(arrays: dict) -> dict:
    return {k: np.asarray(v) for k, v in arrays.items()}


def rmi(arrays: dict, n: int, ranks: np.ndarray) -> np.ndarray:
    """RMI / SY-RMI: root polynomial, one leaf (slope, intercept, eps and
    its two rank fences), then the table window of that leaf."""
    a = _host(arrays)
    r = a["leaf_r"]
    b = len(a["leaf_slope"])
    leaf = np.clip(np.searchsorted(r[:b], np.maximum(ranks, 0), side="right") - 1, 0, b - 1)
    lo = np.maximum(r[leaf] - 1, 0)
    hi = np.minimum(r[leaf + 1], n - 1)
    w = np.minimum(2 * a["leaf_eps"][leaf] + 2, hi - lo + 1)
    model = (
        a["root_coef"].nbytes
        + a["kmin"].nbytes
        + a["inv_span"].nbytes
        + a["leaf_slope"].itemsize
        + a["leaf_icept"].itemsize
        + a["leaf_eps"].itemsize
        + 2 * r.itemsize
    )
    return QUERY_BYTES + model + 8 * ceil_log2(w)


def pgm(arrays: dict, n: int, ranks: np.ndarray) -> np.ndarray:
    """PGM / PGM_M: per level one segment (key, slope and its two rank
    fences), a search of the next level's keys, and at the last level a
    search of the table.  Segments are found bottom-up from the rank."""
    a = _host(arrays)
    sizes, off_r = a["sizes"], a["off_r"]
    eps = int(a["eps"])
    per_level = a["keys"].itemsize + a["slope"].itemsize + 2 * a["rank0"].itemsize
    total = np.full(len(ranks), QUERY_BYTES, dtype=np.int64)
    below = np.maximum(ranks, 0)  # position in the level underneath
    for lvl in range(len(sizes) - 1, -1, -1):
        r0 = a["rank0"][off_r[lvl] : off_r[lvl] + sizes[lvl] + 1]
        seg = np.clip(np.searchsorted(r0[:-1], below, side="right") - 1, 0, sizes[lvl] - 1)
        lo = np.maximum(r0[seg] - 1, 0)
        hi = r0[seg + 1] - 1
        if lvl == len(sizes) - 1:
            hi = np.minimum(hi, n - 1)
        w = np.minimum(2 * (eps + 1) + 2, hi - lo + 1)
        total += per_level + 8 * ceil_log2(w)
        below = seg
    return total


#: the index kinds counted here; a cell whose per-layer metrics need the
#: count refuses any other kind before it builds (``manifest.problems``)
KINDS = {"RMI": rmi, "SY-RMI": rmi, "PGM": pgm, "PGM_M": pgm}


def index_lookup(kind: str, arrays: dict, n: int, ranks: np.ndarray) -> np.ndarray:
    """Bytes per query for one index over a table of ``n`` rows."""
    if kind not in KINDS:
        raise KeyError(f"needed_bytes.py does not count index kind {kind!r}")
    return KINDS[kind](arrays, n, ranks)


def tier_lookup(kind: str, shard_arrays: list, rows: int, offsets: np.ndarray, ranks: np.ndarray):
    """A tier of ``len(shard_arrays)`` shards of ``rows`` padded rows:
    the fence route (every boundary fence), the owner shard's count and
    offset, and that one shard's lookup on its local rank."""
    s = len(shard_arrays)
    owner = np.clip(np.searchsorted(offsets, np.maximum(ranks, 0), side="right") - 1, 0, s - 1)
    total = np.zeros(len(ranks), dtype=np.int64)
    for i, arrays in enumerate(shard_arrays):
        mine = owner == i
        total[mine] = index_lookup(kind, arrays, rows, ranks[mine] - offsets[i])
    return total + 8 * (s - 1) + 16
