"""Sorted Table Search procedures (paper §3.1, Supplementary §1) in JAX.

All procedures are *vectorised over a query batch* and jittable.  The
paper's branchy/branch-free distinction maps onto JAX as follows:

* **branch-free (BFS, BFE, K-BFS)** — fixed trip count ``ceil(log2 n)``
  loops of selects: the native idiom for TPU/XLA (no data-dependent
  control flow at all).  These are the procedures every learned model
  bolts onto.
* **branchy (BBS, K-BBS)** — data-dependent early exit.  A vector machine
  cannot retire lanes early, so BBS is modelled as a ``lax.while_loop``
  that exits when *all* lanes have converged — faithful to the paper's
  semantics, and measurably slower on batched hardware, which is itself a
  finding we report.

Conventions: all public entry points return the **predecessor rank**
``j = rank(x) - 1 in [-1, n-1]`` with ``A[j] <= x < A[j+1]``.  Internal
helpers compute ``upper_bound`` (first index with ``A[i] > x``).
"""

from __future__ import annotations

import math

import jax.numpy as jnp
from jax import lax

from .cdf import ceil_log2
from .limbs import LimbTable, keys_of

#: Predecessor rank reported when ``q`` is smaller than every key —
#: ``rank(x) - 1`` for rank 0.  Every search procedure and index kind
#: shares this sentinel (re-exported by :mod:`repro.dist.sharded_index`).
NO_PRED = -1

# ---------------------------------------------------------------------------
# Branch-free binary search (BFS) — Algorithm 1 of the paper, vectorised.
# ---------------------------------------------------------------------------


def _take(table, idx):
    if isinstance(table, LimbTable):
        return table.take(idx)
    return jnp.take(table, idx, mode="clip")


def bounded_upper_bound(table, q, lo, length, *, steps: int):
    """First index in [lo, lo+length) with table[i] > q; lo+length if none.

    Branch-free: exactly ``steps`` iterations of the Khuong–Morin loop
    (supplementary Algorithm 1) with ``<=`` comparisons, vectorised over
    queries.  ``steps`` must be >= ceil(log2(max length)).
    Zero-length windows return ``lo``.  ``table`` is a u64 array or a
    :class:`~repro.core.limbs.LimbTable`, whose compare is by limbs.
    """
    q = keys_of(table, q)
    base = lo.astype(jnp.int64)
    n = length.astype(jnp.int64)

    def body(_, carry):
        base, n = carry
        half = n >> 1
        mid = base + half
        go_right = (_take(table, mid) <= q) & (n > 1)
        base = jnp.where(go_right, mid, base)
        n = n - jnp.where(n > 1, half, 0)
        return base, n

    base, n = lax.fori_loop(0, steps, body, (base, n))
    ub = base + (_take(table, base) <= q).astype(jnp.int64)
    return jnp.where(length > 0, ub, lo)


def bfs(table, q, *, n: int | None = None):
    """Branch-free Binary Search over the whole table -> predecessor rank."""
    n = int(table.shape[0]) if n is None else n
    lo = jnp.zeros(q.shape, dtype=jnp.int64)
    ln = jnp.full(q.shape, n, dtype=jnp.int64)
    ub = bounded_upper_bound(table, q, lo, ln, steps=ceil_log2(n))
    return ub - 1


def bounded_bfs(table, q, lo, hi, *, max_window: int):
    """Predecessor rank given a guaranteed inclusive window [lo, hi].

    The learned-procedure epilogue: every model feeds its predicted
    interval here.  Guarantee required from the caller: the predecessor
    rank lies in [lo, hi] (lo may be -1, meaning "possibly before A[0]").
    """
    n = table.shape[0]
    lo_c = jnp.clip(lo, 0, n - 1).astype(jnp.int64)
    hi_c = jnp.clip(hi, 0, n - 1).astype(jnp.int64)
    length = jnp.maximum(hi_c - lo_c + 1, 0)
    ub = bounded_upper_bound(table, q, lo_c, length, steps=ceil_log2(max_window))
    return ub - 1


def bounded_bbs_branchy(table, q, lo, hi):
    """Branchy bounded epilogue (the paper's \\*-BBS variants).

    Early-exit while_loop over a guaranteed inclusive window [lo, hi]:
    all lanes iterate until every lane has converged — the vectorised
    semantics of the paper's scalar branchy loop.  Shared by the
    ``backend="bbs"`` path of every :class:`repro.index.Index` kind.
    ``table`` is a u64 array or a :class:`~repro.core.limbs.LimbTable`.
    """
    n = table.shape[0]
    res0 = jnp.full(q.shape, NO_PRED, dtype=jnp.int64)
    active0 = jnp.ones(q.shape, dtype=bool)
    lo = jnp.clip(lo.astype(jnp.int64), 0, n - 1)
    hi = jnp.clip(hi.astype(jnp.int64), 0, n - 1)
    qk = keys_of(table, q)

    def cond(state):
        return jnp.any(state[3])

    def body(state):
        lo, hi, res, active = state
        mid = (lo + hi) >> 1
        v = _take(table, mid)
        found = active & (v == qk)
        res = jnp.where(found, mid, res)
        go_right = v < qk
        lo_n = jnp.where(active & go_right, mid + 1, lo)
        hi_n = jnp.where(active & ~go_right, mid - 1, hi)
        res = jnp.where(active & ~found & (lo_n > hi_n), hi_n, res)
        active = active & ~found & (lo_n <= hi_n)
        return lo_n, hi_n, res, active

    _, _, res, _ = lax.while_loop(cond, body, (lo, hi, res0, active0))
    return res


def bounded_upper_bound_branchy(table, q, lo, count):
    """Branchy counterpart of :func:`bounded_upper_bound` for prefix
    windows: the number of keys ``<= q`` among ``table[lo : lo+count]``,
    in ``[0, count]``, via the early-exit BBS loop.

    The two-tier updatable read path (``GAPPED``) uses this on both its
    gapped-leaf valid prefix and its delta-buffer valid prefix under
    ``backend="bbs"``; ``count`` may be zero (empty leaf / empty delta),
    which the clamp resolves to 0 regardless of what pad slots the probe
    touched.  Assumes unique keys within the window (the equality early
    exit identifies *the* match).
    """
    lo = lo.astype(jnp.int64)
    count = count.astype(jnp.int64)
    res = bounded_bbs_branchy(table, q, lo, lo + count - 1)
    return jnp.clip(res - lo + 1, 0, count)


# ---------------------------------------------------------------------------
# Branchy binary search (BBS) — early-exit semantics via while_loop.
# ---------------------------------------------------------------------------


def bbs(table, q, *, n: int | None = None):
    """Branchy Binary Search: classic lo/hi loop with equality early exit.

    All lanes iterate until every lane has converged (vector semantics of
    a branchy scalar loop)."""
    n = int(table.shape[0]) if n is None else n
    lo0 = jnp.zeros(q.shape, dtype=jnp.int64)
    hi0 = jnp.full(q.shape, n - 1, dtype=jnp.int64)
    res0 = jnp.full(q.shape, NO_PRED, dtype=jnp.int64)
    active0 = jnp.ones(q.shape, dtype=bool)

    def cond(state):
        _, _, _, active = state
        return jnp.any(active)

    def body(state):
        lo, hi, res, active = state
        mid = (lo + hi) >> 1
        v = _take(table, mid)
        found = active & (v == q)
        res = jnp.where(found, mid, res)
        go_right = v < q
        lo_n = jnp.where(active & go_right, mid + 1, lo)
        hi_n = jnp.where(active & ~go_right, mid - 1, hi)
        active_n = active & ~found & (lo_n <= hi_n)
        # On exhaustion the predecessor is hi (last index with A[i] < q).
        res = jnp.where(active & ~found & ~(lo_n <= hi_n), hi_n, res)
        return lo_n, hi_n, res, active_n

    _, _, res, _ = lax.while_loop(cond, body, (lo0, hi0, res0, active0))
    # Equality hits return the matched index; duplicates are deduped at
    # build time so the match *is* the predecessor.
    return res


# ---------------------------------------------------------------------------
# Eytzinger layout (BFE) — supplementary Algorithm 3.
# ---------------------------------------------------------------------------


def eytzinger_layout(table_np):
    """Host-side: permute sorted table into Eytzinger (BFS tree) order.

    Returns (layout, inorder_rank, height).  The layout is padded to
    2^h - 1 entries with the max key so the tree is perfect; the
    closed-form in-order rank of each node vectorises the construction
    and provides the position->sorted-rank map the search epilogue needs
    (Khuong–Morin's recovery yields a *layout* position).
    """
    import numpy as np

    n = int(table_np.shape[0])
    h = max(1, int(math.ceil(math.log2(n + 1))))
    m = (1 << h) - 1
    pad = np.full(m, np.iinfo(np.uint64).max, dtype=np.uint64)
    pad[:n] = table_np
    k = np.arange(m, dtype=np.int64)
    d = np.floor(np.log2(k + 1)).astype(np.int64)  # depth
    # in-order rank of eytzinger node k in a perfect tree of height h
    rank = (2 * (k + 1 - (1 << d)) + 1) * (1 << (h - 1 - d)) - 1
    layout = pad[rank]
    return layout, rank, h


def bfe(layout, inorder_rank, q, *, height: int, n: int):
    """Branch-free Eytzinger search -> predecessor rank (paper Alg. 3).

    ``layout``/``inorder_rank`` come from :func:`eytzinger_layout`; uses
    ``q < A[i]`` so the walk computes upper_bound; the ffs bit-trick
    recovers the *layout* position of the successor, mapped to a sorted
    rank via ``inorder_rank``.
    """
    i = jnp.zeros(q.shape, dtype=jnp.int64)

    def body(_, i):
        v = _take(layout, i)
        return jnp.where(q < v, 2 * i + 1, 2 * i + 2)

    i = lax.fori_loop(0, height, body, i)
    t = i + 1
    # j = t >> ffs(~t); ffs(~t) = 1 + (number of trailing one bits of t)
    low_zero = (~t) & (t + 1)  # isolate lowest zero bit of t
    trailing_ones = lax.population_count(low_zero - 1)
    j = t >> (trailing_ones + 1)
    m = jnp.int64(layout.shape[0])
    ub = jnp.where(j == 0, m, _take(inorder_rank, jnp.maximum(j - 1, 0)))
    # ub indexes the padded sorted order; clamp pads back to n
    return jnp.minimum(ub, n) - 1


# ---------------------------------------------------------------------------
# k-ary search (K-BFS) — supplementary Algorithm 2, plus the TPU-native
# lane-wide variant (k = 128) used by the Pallas kernels.
# ---------------------------------------------------------------------------


def bounded_kary_upper_bound(table, q, lo, length, *, k: int, steps: int):
    """Upper bound via k-ary splitting: each step gathers k-1 fences and
    reduces the window by ~k.  steps >= ceil(log_k(max length))."""
    base = lo.astype(jnp.int64)
    n = length.astype(jnp.int64)
    frac = jnp.arange(1, k, dtype=jnp.int64)

    def body(_, carry):
        base, n = carry
        fence = base[..., None] + (frac * n[..., None]) // k
        v = _take(table, fence)
        seg = jnp.sum((v <= q[..., None]).astype(jnp.int64), axis=-1)
        new_base = base + (seg * n) // k
        new_n = (jnp.minimum(seg + 1, k) * n) // k - (seg * n) // k
        keep = n > 1
        base = jnp.where(keep, new_base, base)
        n = jnp.where(keep, new_n, n)
        return base, n

    base, n = lax.fori_loop(0, steps, body, (base, n))
    ub = base + (_take(table, base) <= q).astype(jnp.int64)
    return jnp.where(length > 0, ub, lo)


def kbfs(table, q, *, k: int = 6, n: int | None = None):
    """k-ary branch-free search -> predecessor rank (paper's K-BFS)."""
    n = int(table.shape[0]) if n is None else n
    steps = max(1, int(math.ceil(math.log(max(n, 2)) / math.log(k))))
    lo = jnp.zeros(q.shape, dtype=jnp.int64)
    ln = jnp.full(q.shape, n, dtype=jnp.int64)
    ub = bounded_kary_upper_bound(table, q, lo, ln, k=k, steps=steps)
    return ub - 1


def kbbs(table, q, *, k: int = 6, n: int | None = None):
    """Branchy k-ary search: while_loop until all lanes have window<=1."""
    n = int(table.shape[0]) if n is None else n
    frac = jnp.arange(1, k, dtype=jnp.int64)
    base0 = jnp.zeros(q.shape, dtype=jnp.int64)
    n0 = jnp.full(q.shape, n, dtype=jnp.int64)

    def cond(carry):
        _, ln = carry
        return jnp.any(ln > 1)

    def body(carry):
        base, ln = carry
        fence = base[..., None] + (frac * ln[..., None]) // k
        v = _take(table, fence)
        seg = jnp.sum((v <= q[..., None]).astype(jnp.int64), axis=-1)
        new_base = base + (seg * ln) // k
        new_n = (jnp.minimum(seg + 1, k) * ln) // k - (seg * ln) // k
        keep = ln > 1
        return jnp.where(keep, new_base, base), jnp.where(keep, new_n, ln)

    base, _ = lax.while_loop(cond, body, (base0, n0))
    ub = base + (_take(table, base) <= q).astype(jnp.int64)
    return ub - 1


# ---------------------------------------------------------------------------
# Interpolation search (IBS) and 3-point interpolation (TIP).
# ---------------------------------------------------------------------------


def ibs(table, q, *, n: int | None = None, max_steps: int = 16):
    """Interpolation search: ``max_steps`` fixed interpolation rounds with
    masking, then a branch-free binary epilogue on the surviving window.
    Matches classic IBS on uniform data in O(loglog n) effective rounds."""
    n = int(table.shape[0]) if n is None else n
    lo = jnp.zeros(q.shape, dtype=jnp.int64)
    hi = jnp.full(q.shape, n - 1, dtype=jnp.int64)

    def body(_, carry):
        lo, hi = carry
        a_lo = _take(table, lo).astype(jnp.float64)
        a_hi = _take(table, hi).astype(jnp.float64)
        qe = q.astype(jnp.float64)
        denom = jnp.maximum(a_hi - a_lo, 1.0)
        pos = lo + ((qe - a_lo) * (hi - lo).astype(jnp.float64) / denom).astype(jnp.int64)
        pos = jnp.clip(pos, lo, hi)
        v = _take(table, pos)
        go_right = v <= q
        new_lo = jnp.where(go_right, pos + 1, lo)
        new_hi = jnp.where(go_right, hi, pos - 1)
        keep = lo <= hi
        return jnp.where(keep, new_lo, lo), jnp.where(keep, new_hi, hi)

    lo, hi = lax.fori_loop(0, max_steps, body, (lo, hi))
    # After interpolation rounds, predecessor is in [lo-1, hi] (loop
    # invariant: everything < lo is <= q, everything > hi is > q).
    win_lo = jnp.maximum(lo - 1, 0)
    length = jnp.maximum(hi - win_lo + 1, 0)
    ub = bounded_upper_bound(table, q, win_lo, jnp.maximum(length, 1), steps=ceil_log2(n))
    return jnp.where(length > 0, ub - 1, hi)


def tip(table, q, *, n: int | None = None, max_steps: int = 8, guard: int = 8):
    """Three-point interpolation (Van Sandt et al.) — fixed-round variant.

    Uses quadratic (3-point) interpolation of the key->rank curve; falls
    back to the branch-free epilogue once the window is below ``guard``.
    """
    n = int(table.shape[0]) if n is None else n
    lo = jnp.zeros(q.shape, dtype=jnp.int64)
    hi = jnp.full(q.shape, n - 1, dtype=jnp.int64)

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi) >> 1
        y0 = _take(table, lo).astype(jnp.float64) - q.astype(jnp.float64)
        y1 = _take(table, mid).astype(jnp.float64) - q.astype(jnp.float64)
        y2 = _take(table, hi).astype(jnp.float64) - q.astype(jnp.float64)
        dm = (mid - lo).astype(jnp.float64)
        num = y1 * dm * (1.0 + (y0 - y1) / jnp.where(y1 == y2, 1.0, y1 - y2))
        den = y0 - y2 * ((y0 - y1) / jnp.where(y1 == y2, 1.0, y1 - y2))
        expected = mid + (num / jnp.where(den == 0, 1.0, den)).astype(jnp.int64)
        expected = jnp.clip(expected, lo, hi)
        v = _take(table, expected)
        go_right = v <= q
        new_lo = jnp.where(go_right, expected + 1, lo)
        new_hi = jnp.where(go_right, hi, expected - 1)
        keep = (hi - lo) > guard
        return jnp.where(keep, new_lo, lo), jnp.where(keep, new_hi, hi)

    lo, hi = lax.fori_loop(0, max_steps, body, (lo, hi))
    win_lo = jnp.maximum(lo - 1, 0)
    length = jnp.maximum(hi - win_lo + 1, 0)
    ub = bounded_upper_bound(table, q, win_lo, jnp.maximum(length, 1), steps=ceil_log2(n))
    return jnp.where(length > 0, ub - 1, hi)


# ---------------------------------------------------------------------------
# Registry of plain (model-free) procedures.
# ---------------------------------------------------------------------------

PROCEDURES = {
    "bfs": bfs,
    "bbs": bbs,
    "kbfs": kbfs,
    "kbbs": kbbs,
    "ibs": ibs,
    "tip": tip,
}
