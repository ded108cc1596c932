"""Per-kind build + query implementations behind the :class:`Index` API.

Each kind contributes:

* a **build** function (registered via :mod:`repro.index.registry`) that
  runs the existing fitting code in :mod:`repro.core` and flattens the
  resulting model into the Index's array leaves + static aux;
* a **query impl** (:data:`QUERY_IMPLS`) with ``intervals`` /
  ``epi_steps`` / ``space_bytes`` / ``pallas`` / ``pallas_batched``
  operating purely on the array leaves — the data-driven form of the
  old per-class methods.  ``pallas`` is the kind's fused kernel where
  one exists (RMI family, PGM family, RS) and the lane-wide k-ary
  kernel otherwise; ``pallas_batched`` is its ``(table, q_tile)``-grid
  batched counterpart used by tiers and batches.

Two deliberate normalisations make jit caches collide across instances:

* variable-length leaves (PGM levels, RS knots) are padded to the next
  power of two with inert sentinels (max-key / repeated last entry), so
  same-kind indexes over different tables share leaf shapes far more
  often;
* every bounded-search trip count is rounded up to a multiple of 4
  (:func:`_bucket_steps`) — extra iterations of the Khuong–Morin loop
  are no-ops once the window reaches width 1, so this trades a few idle
  gathers for one shared trace per kind.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable

import numpy as np
import jax.numpy as jnp

from repro.core import search
from repro.core.atomic import build_atomic, poly_eval_jnp
from repro.core.btree import build_btree
from repro.core.cdf import POS_DTYPE, ceil_log2
from repro.core.kbfs import build_ko
from repro.core.pgm import build_pgm, build_pgm_bicriteria
from repro.core.radix_spline import build_rs
from repro.core.rmi import build_rmi
from repro.core.sy_rmi import build_sy_rmi
from repro.kernels import ops as kernel_ops

from .index import Index
from .registry import register
from .specs import (
    AtomicSpec,
    BTreeSpec,
    KOSpec,
    PGMBicriteriaSpec,
    PGMSpec,
    RMISpec,
    RSSpec,
    SYRMISpec,
)

_MAXKEY = np.uint64(np.iinfo(np.uint64).max)


def _bucket_steps(window: int) -> int:
    """ceil_log2 rounded up to a multiple of 4 (jit-cache bucketing)."""
    s = ceil_log2(max(int(window), 2))
    return max(4, 4 * math.ceil(s / 4))


def _pow2ceil(x: int) -> int:
    x = max(int(x), 1)
    return 1 << (x - 1).bit_length()


def _pad_pow2(arr: np.ndarray, fill) -> np.ndarray:
    arr = np.asarray(arr)
    m = _pow2ceil(arr.shape[0])
    if m == arr.shape[0]:
        return arr
    pad = np.full(m - arr.shape[0], fill, dtype=arr.dtype)
    return np.concatenate([arr, pad])


def _scalar(x, dtype):
    return jnp.asarray(np.asarray(x), dtype=dtype).reshape(())


# ---------------------------------------------------------------------------
# Query impls
# ---------------------------------------------------------------------------


@dataclass
class QueryImpl:
    intervals: Callable  # (index, table, q) -> (lo, hi)
    space_bytes: Callable  # (index) -> int
    pallas: Callable = None  # (index, table, q) -> ranks
    pallas_batched: Callable = None  # (stacked index, tables, queries) -> ranks
    epi_key: str = "epi"
    #: full lookup override — ``(index, table, q, backend) -> ranks``.
    #: Kinds whose answer is not "interval + bounded search over ``table``"
    #: (GAPPED: self-contained two-tier merge) set this; ``lookup_impl``
    #: dispatches to it before any generic backend handling.
    lookup: Callable = None
    #: the backends this kind honestly supports (R4 probes the claim and
    #: docs/backends.md documents it; ``Index.lookup`` enforces it)
    backends: tuple = ("xla", "bbs", "pallas", "ref")

    def __post_init__(self):
        # kinds without a fused batched kernel answer tiers/batches with
        # the model-free batched k-ary kernel (exact, shared trace)
        if self.pallas_batched is None and "pallas" in self.backends:
            self.pallas_batched = _kary_pallas_batched

    def epi_steps(self, index: Index) -> int:
        return index.s(self.epi_key)


def _pad_queries(arrs, tile: int, axis: int = 0):
    """Zero-pad query-shaped arrays to a tile multiple along ``axis``."""
    nq = arrs[0].shape[axis]
    pad = (-nq) % tile
    if pad == 0:
        return arrs
    widths = [(0, 0)] * arrs[0].ndim
    widths[axis] = (0, pad)
    return [jnp.pad(a, widths) for a in arrs]


def _kary_pallas_fallback(index: Index, table, q):
    """Model-free lane-wide k-ary kernel: the TPU-native K-BFS baseline
    for kinds without a fused kernel (returns exact predecessor ranks)."""
    from repro.kernels.kary_search import kary_search_pallas, LANES
    from repro.kernels.ops import split_u64

    thi, tlo = split_u64(table)
    qhi, qlo = split_u64(q)
    nq = q.shape[0]
    tile = min(512, _pow2ceil(nq))
    qhi, qlo = _pad_queries([qhi, qlo], tile)
    interpret = kernel_ops._interpret()
    out = kary_search_pallas(qhi, qlo, thi, tlo, k=LANES, tile_q=tile, interpret=interpret)
    return out[:nq].astype(POS_DTYPE)


def _kary_pallas_batched(index: Index, tables, queries):
    """Batched k-ary kernel over ``(n_tables, m)`` tables: the Pallas
    tier/batch baseline for kinds without a fused batched kernel."""
    from repro.kernels.kary_search import batched_kary_search_pallas, LANES
    from repro.kernels.ops import split_u64

    thi, tlo = split_u64(tables)
    qhi, qlo = split_u64(queries)
    nq = queries.shape[1]
    tile = min(512, _pow2ceil(nq))
    qhi, qlo = _pad_queries([qhi, qlo], tile, axis=1)
    interpret = kernel_ops._interpret()
    out = batched_kary_search_pallas(qhi, qlo, thi, tlo, k=LANES, tile_q=tile, interpret=interpret)
    return out[:, :nq].astype(POS_DTYPE)


# -- atomic (L / Q / C) ------------------------------------------------------


def _atomic_intervals(idx: Index, table, q):
    a = idx.arrays
    n = table.shape[0]
    eps = a["eps"]
    u = jnp.clip((q.astype(jnp.float64) - a["kmin"]) * a["inv_span"], 0.0, 1.0)
    p = jnp.clip(poly_eval_jnp(a["coef"], u), -4.0e15, 4.0e15)
    lo = jnp.floor(p).astype(POS_DTYPE) - eps
    hi = jnp.ceil(p).astype(POS_DTYPE) + eps
    return jnp.clip(lo, 0, n - 1), jnp.clip(hi, 0, n - 1)


def _atomic_space(idx: Index) -> int:
    # coef valid prefix (degree+1 of the padded 4) + kmin/inv_span + eps
    a = idx.arrays
    return 8 * (idx.s("degree") + 1) + a["kmin"].nbytes + a["inv_span"].nbytes + a["eps"].nbytes


ATOMIC_IMPL = QueryImpl(
    intervals=_atomic_intervals, space_bytes=_atomic_space, pallas=_kary_pallas_fallback
)


def _build_atomic_index(spec: AtomicSpec, table_np: np.ndarray) -> Index:
    m = build_atomic(table_np, degree=spec.degree)
    arrays = {
        "coef": jnp.asarray(m.coef, jnp.float64),
        "kmin": _scalar(m.kmin, jnp.float64),
        "inv_span": _scalar(m.inv_span, jnp.float64),
        "eps": _scalar(m.eps, jnp.int64),
    }
    static = (("degree", spec.degree), ("epi", _bucket_steps(min(2 * m.eps + 3, m.n))))
    info = {"name": m.name, "build_time": m.build_time, "eps": m.eps, "n": m.n}
    return Index(spec.kind, static, arrays, info)


# -- KO ----------------------------------------------------------------------


def _ko_intervals(idx: Index, table, q):
    a = idx.arrays
    fences = a["fences"]
    s = jnp.sum((q[..., None] >= fences[None, :]).astype(POS_DTYPE), axis=-1)
    coef = jnp.take(a["coef"], s, axis=0)
    kmin = jnp.take(a["kmin_seg"], s)
    inv_span = jnp.take(a["inv_span_seg"], s)
    eps = jnp.take(a["eps"], s)
    u = jnp.clip((q.astype(jnp.float64) - kmin) * inv_span, 0.0, 1.0)
    p = jnp.clip(poly_eval_jnp(coef, u), -4.0e15, 4.0e15)
    lo = jnp.floor(p).astype(POS_DTYPE) - eps
    hi = jnp.ceil(p).astype(POS_DTYPE) + eps
    b_lo = jnp.maximum(jnp.take(a["seg_start"], s) - 1, 0)
    b_hi = jnp.take(a["seg_start"], s + 1) - 1
    return jnp.clip(lo, b_lo, b_hi), jnp.clip(hi, b_lo, b_hi)


def _ko_space(idx: Index) -> int:
    a = idx.arrays
    return sum(
        a[k].nbytes
        for k in ("fences", "coef", "kmin_seg", "inv_span_seg", "eps", "seg_start")
    )


KO_IMPL = QueryImpl(intervals=_ko_intervals, space_bytes=_ko_space, pallas=_kary_pallas_fallback)


def _build_ko_index(spec: KOSpec, table_np: np.ndarray) -> Index:
    m = build_ko(table_np, k=spec.k)
    arrays = {
        "fences": jnp.asarray(m.fences),
        "coef": jnp.asarray(m.coef),
        "kmin_seg": jnp.asarray(m.kmin_seg),
        "inv_span_seg": jnp.asarray(m.inv_span_seg),
        "eps": jnp.asarray(m.eps),
        "seg_start": jnp.asarray(m.seg_start),
    }
    static = (("epi", _bucket_steps(m.max_window)),)
    info = {
        "name": m.name,
        "build_time": m.build_time,
        "k": m.k,
        "max_eps": m.max_eps,
        "n": m.n,
    }
    return Index(spec.kind, static, arrays, info)


# -- RMI / SY-RMI ------------------------------------------------------------


def _rmi_intervals(idx: Index, table, q):
    a = idx.arrays
    n = table.shape[0]
    b = a["leaf_slope"].shape[0]
    u = jnp.clip((q.astype(jnp.float64) - a["kmin"]) * a["inv_span"], 0.0, 1.0)
    p_root = jnp.clip(poly_eval_jnp(a["root_coef"], u), -4.0e15, 4.0e15)
    leaf = jnp.clip(jnp.floor(p_root * (b / n)).astype(POS_DTYPE), 0, b - 1)
    slope = jnp.take(a["leaf_slope"], leaf)
    icept = jnp.take(a["leaf_icept"], leaf)
    eps = jnp.take(a["leaf_eps"], leaf)
    p = jnp.clip(slope * u + icept, -4.0e15, 4.0e15)
    lo = jnp.floor(p).astype(POS_DTYPE) - eps
    hi = jnp.ceil(p).astype(POS_DTYPE) + eps
    # high fence is r_{l+1}, not r_{l+1} - 1: tolerates a 1-ulp root-eval
    # divergence between build (NumPy) and query (XLA) flipping floor()
    # at a leaf boundary — the extended eps covers the boundary key.
    b_lo = jnp.maximum(jnp.take(a["leaf_r"], leaf) - 1, 0)
    b_hi = jnp.minimum(jnp.take(a["leaf_r"], leaf + 1), n - 1)
    return jnp.clip(lo, b_lo, b_hi), jnp.clip(hi, b_lo, b_hi)


def _rmi_space(idx: Index) -> int:
    # the k_* leaves are the fused kernel's f32 re-encoding of the same
    # model — a query-time cache, not model space, so they don't count
    a = idx.arrays
    return sum(
        a[k].nbytes
        for k in ("root_coef", "leaf_slope", "leaf_icept", "leaf_eps", "leaf_r", "kmin", "inv_span")
    )


def _rmi_pallas(idx: Index, table, q):
    """Fused predict+search Pallas kernel; the f32/i32 re-encoding was
    folded into the Index leaves at build time (``k_*`` arrays)."""
    from repro.kernels.ops import split_u64
    from repro.kernels.rmi_search import fused_rmi_search_pallas

    a = idx.arrays
    u = jnp.clip((q.astype(jnp.float64) - a["kmin"]) * a["inv_span"], 0.0, 1.0).astype(
        jnp.float32
    )
    qhi, qlo = split_u64(q)
    thi, tlo = split_u64(table)
    nq = q.shape[0]
    tile = min(512, _pow2ceil(nq))
    u, qhi, qlo = _pad_queries([u, qhi, qlo], tile)
    out = fused_rmi_search_pallas(
        u,
        qhi,
        qlo,
        thi,
        tlo,
        a["k_root"],
        a["k_slope"],
        a["k_icept"],
        a["k_eps"],
        a["k_rlo"],
        a["k_rhi"],
        steps=idx.s("ksteps"),
        tile_q=tile,
        interpret=kernel_ops._interpret(),
    )
    return out[:nq].astype(POS_DTYPE)


def _rmi_pallas_batched(idx: Index, tables, queries):
    """Batched fused RMI kernel: grid over (table, q_tile), per-table
    parameter blocks from the stacked ``k_*`` leaves.  The bucketed
    ``ksteps`` static took the max across tables at stack time, so one
    trip count covers the widest per-table window."""
    from repro.kernels.ops import split_u64
    from repro.kernels.rmi_search import batched_rmi_search_pallas

    a = idx.arrays
    u = jnp.clip(
        (queries.astype(jnp.float64) - a["kmin"][:, None]) * a["inv_span"][:, None],
        0.0,
        1.0,
    ).astype(jnp.float32)
    qhi, qlo = split_u64(queries)
    thi, tlo = split_u64(tables)
    nq = queries.shape[1]
    tile = min(512, _pow2ceil(nq))
    u, qhi, qlo = _pad_queries([u, qhi, qlo], tile, axis=1)
    out = batched_rmi_search_pallas(
        u,
        qhi,
        qlo,
        thi,
        tlo,
        a["k_root"],
        a["k_slope"],
        a["k_icept"],
        a["k_eps"],
        a["k_rlo"],
        a["k_rhi"],
        steps=idx.s("ksteps"),
        tile_q=tile,
        interpret=kernel_ops._interpret(),
    )
    return out[:, :nq].astype(POS_DTYPE)


RMI_IMPL = QueryImpl(
    intervals=_rmi_intervals,
    space_bytes=_rmi_space,
    pallas=_rmi_pallas,
    pallas_batched=_rmi_pallas_batched,
)


def rmi_model_to_index(kind: str, m, table_np: np.ndarray, extra_info=None) -> Index:
    """Wrap an already-fitted :class:`repro.core.rmi.RMIModel` as an
    Index without refitting (sweep reuse, e.g. CDFShop's candidates)."""
    return _rmi_to_index(kind, m, table_np, extra_info)


def _rmi_to_index(kind: str, m, table_np: np.ndarray, extra_info=None) -> Index:
    from repro.kernels.ops import rmi_kernel_arrays

    karr, ksteps = rmi_kernel_arrays(m, table_np)
    arrays = {
        "root_coef": jnp.asarray(m.root_coef),
        "leaf_slope": jnp.asarray(m.leaf_slope),
        "leaf_icept": jnp.asarray(m.leaf_icept),
        "leaf_eps": jnp.asarray(m.leaf_eps),
        "leaf_r": jnp.asarray(m.leaf_r),
        "kmin": _scalar(m.kmin, jnp.float64),
        "inv_span": _scalar(m.inv_span, jnp.float64),
        "k_root": jnp.asarray(karr["root"]),
        "k_slope": jnp.asarray(karr["slope"]),
        "k_icept": jnp.asarray(karr["icept"]),
        "k_eps": jnp.asarray(karr["eps"]),
        "k_rlo": jnp.asarray(karr["rlo"]),
        "k_rhi": jnp.asarray(karr["rhi"]),
    }
    static = (("epi", _bucket_steps(m.max_window)), ("ksteps", _bucket_steps(1 << ksteps)))
    info = {
        "name": m.name,
        "build_time": m.build_time,
        "b": m.b,
        "max_eps": m.max_eps,
        "root_type": m.root_type,
        "n": m.n,
    }
    info.update(extra_info or {})
    return Index(kind, static, arrays, info)


def _build_rmi_index(spec: RMISpec, table_np: np.ndarray) -> Index:
    m = build_rmi(table_np, b=spec.b, root_type=spec.root_type)
    return _rmi_to_index(spec.kind, m, table_np)


def _build_sy_rmi_index(spec: SYRMISpec, table_np: np.ndarray) -> Index:
    m = build_sy_rmi(
        table_np, space_pct=spec.space_pct, ub=spec.ub, winner_root=spec.winner_root
    )
    return _rmi_to_index(spec.kind, m, table_np, {"space_pct": spec.space_pct})


# -- PGM / PGM_M -------------------------------------------------------------


def _pgm_intervals(idx: Index, table, q):
    a = idx.arrays
    n = table.shape[0]
    levels = idx.s("levels")
    steps = idx.s("epi")
    eps = a["eps"]
    qf = q.astype(jnp.float64)
    seg = jnp.zeros(q.shape, dtype=POS_DTYPE)
    for lvl in range(levels):
        off = a["off"][lvl]
        off_r = a["off_r"][lvl]
        x0 = jnp.take(a["keys"], off + seg).astype(jnp.float64)
        slope = jnp.take(a["slope"], off + seg)
        r0 = jnp.take(a["rank0"], off_r + seg)
        pred = r0.astype(jnp.float64) + slope * jnp.maximum(qf - x0, 0.0)
        pred = jnp.clip(pred, -1.0, 4.0e15)
        b_lo = jnp.maximum(r0 - 1, 0)
        b_hi = jnp.take(a["rank0"], off_r + seg + 1) - 1
        lo = jnp.clip(jnp.floor(pred).astype(POS_DTYPE) - (eps + 1), b_lo, b_hi)
        hi = jnp.clip(jnp.ceil(pred).astype(POS_DTYPE) + (eps + 1), b_lo, b_hi)
        if lvl + 1 < levels:
            off_n = a["off"][lvl + 1]
            size_n = a["sizes"][lvl + 1]
            length = jnp.maximum(hi - lo + 1, 1)
            ub = search.bounded_upper_bound(a["keys"], q, off_n + lo, length, steps=steps)
            seg = jnp.clip(ub - off_n - 1, 0, size_n - 1)
        else:
            return jnp.clip(lo, 0, n - 1), jnp.clip(hi, 0, n - 1)
    raise AssertionError("unreachable")


def _pgm_space(idx: Index) -> int:
    # valid prefixes of the level-concatenated leaves (the pow2 sentinel
    # pad is jit-cache bucketing, not model space) + level directories
    a = idx.arrays
    sizes = np.asarray(a["sizes"])
    kv, rv = int(sizes.sum()), int((sizes + 1).sum())
    per_seg = kv * (a["keys"].dtype.itemsize + a["slope"].dtype.itemsize)
    ranks = rv * a["rank0"].dtype.itemsize
    meta = a["off"].nbytes + a["off_r"].nbytes + a["sizes"].nbytes + a["eps"].nbytes
    return per_seg + ranks + meta


def _pgm_pallas(idx: Index, table, q):
    """Fused PGM descent (root route + per-level segment gather +
    ε-window search); the f32 re-anchored segment models were folded
    into the Index leaves at build time (``pk_*`` arrays)."""
    from repro.kernels.ops import split_u64
    from repro.kernels.pgm_search import fused_pgm_search_pallas

    a = idx.arrays
    u = jnp.clip((q.astype(jnp.float64) - a["pk_kmin"]) * a["pk_inv_span"], 0.0, 1.0).astype(
        jnp.float32
    )
    qhi, qlo = split_u64(q)
    thi, tlo = split_u64(table)
    khi, klo = split_u64(a["keys"])
    nq = q.shape[0]
    tile = min(512, _pow2ceil(nq))
    u, qhi, qlo = _pad_queries([u, qhi, qlo], tile)
    out = fused_pgm_search_pallas(
        u,
        qhi,
        qlo,
        thi,
        tlo,
        khi,
        klo,
        a["pk_u0"],
        a["pk_slope"],
        a["rank0"].astype(jnp.int32),
        a["off"].astype(jnp.int32),
        a["off_r"].astype(jnp.int32),
        a["sizes"].astype(jnp.int32),
        a["pk_eps"].reshape(1),
        levels=idx.s("levels"),
        steps=idx.s("pksteps"),
        tile_q=tile,
        interpret=kernel_ops._interpret(),
    )
    return out[:nq].astype(POS_DTYPE)


def _pgm_pallas_batched(idx: Index, tables, queries):
    """Batched fused PGM descent: grid over (table, q_tile), per-table
    leaf/directory blocks from the stacked arrays.  The lifted level
    structure is common across tables (``_lift_pgm_levels``) and the
    bucketed ``pksteps`` static took the max at stack time, so one trip
    count covers the widest per-table window."""
    from repro.kernels.ops import split_u64
    from repro.kernels.pgm_search import batched_pgm_search_pallas

    a = idx.arrays
    u = jnp.clip(
        (queries.astype(jnp.float64) - a["pk_kmin"][:, None]) * a["pk_inv_span"][:, None],
        0.0,
        1.0,
    ).astype(jnp.float32)
    qhi, qlo = split_u64(queries)
    thi, tlo = split_u64(tables)
    khi, klo = split_u64(a["keys"])
    nq = queries.shape[1]
    tile = min(512, _pow2ceil(nq))
    u, qhi, qlo = _pad_queries([u, qhi, qlo], tile, axis=1)
    out = batched_pgm_search_pallas(
        u,
        qhi,
        qlo,
        thi,
        tlo,
        khi,
        klo,
        a["pk_u0"],
        a["pk_slope"],
        a["rank0"].astype(jnp.int32),
        a["off"].astype(jnp.int32),
        a["off_r"].astype(jnp.int32),
        a["sizes"].astype(jnp.int32),
        a["pk_eps"].reshape(-1, 1),
        levels=idx.s("levels"),
        steps=idx.s("pksteps"),
        tile_q=tile,
        interpret=kernel_ops._interpret(),
    )
    return out[:, :nq].astype(POS_DTYPE)


PGM_IMPL = QueryImpl(
    intervals=_pgm_intervals,
    space_bytes=_pgm_space,
    pallas=_pgm_pallas,
    pallas_batched=_pgm_pallas_batched,
)


def pgm_model_to_index(kind: str, m, table_np: np.ndarray, extra_info=None) -> Index:
    """Wrap an already-fitted :class:`repro.core.pgm.PGMModel` as an
    Index without refitting (the batched scan-fit path)."""
    return _pgm_to_index(kind, m, table_np, extra_info)


def _pgm_to_index(kind: str, m, table_np: np.ndarray, extra_info=None) -> Index:
    from repro.kernels.ops import pgm_kernel_arrays

    karr, pksteps = pgm_kernel_arrays(m, table_np)
    level_keys = [np.asarray(k) for k in m.level_keys]
    level_slope = [np.asarray(s) for s in m.level_slope]
    level_rank0 = [np.asarray(r) for r in m.level_rank0]
    sizes = np.asarray(m.level_sizes, dtype=np.int64)
    off = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    off_r = np.concatenate([[0], np.cumsum(sizes + 1)]).astype(np.int64)
    keys = np.concatenate(level_keys)
    slope = np.concatenate(level_slope)
    rank0 = np.concatenate(level_rank0)
    arrays = {
        "keys": jnp.asarray(_pad_pow2(keys, _MAXKEY)),
        "slope": jnp.asarray(_pad_pow2(slope, 0.0)),
        "rank0": jnp.asarray(_pad_pow2(rank0, rank0[-1])),
        "off": jnp.asarray(off),
        "off_r": jnp.asarray(off_r),
        "sizes": jnp.asarray(sizes),
        "eps": _scalar(m.eps, jnp.int64),
        # fused-kernel re-encoding (query-time cache, not model space)
        "pk_u0": jnp.asarray(_pad_pow2(karr["u0"], np.float32(1.0))),
        "pk_slope": jnp.asarray(_pad_pow2(karr["slope"], np.float32(0.0))),
        "pk_eps": _scalar(karr["eps"], jnp.int32),
        "pk_kmin": _scalar(karr["kmin"], jnp.float64),
        "pk_inv_span": _scalar(karr["inv_span"], jnp.float64),
    }
    static = (
        ("levels", len(level_keys)),
        ("epi", _bucket_steps(min(2 * (m.eps + 2) + 3, m.n))),
        ("pksteps", _bucket_steps(1 << pksteps)),
    )
    info = {
        "name": m.name,
        "build_time": m.build_time,
        "eps": m.eps,
        "n_segments_l0": m.n_segments_l0,
        "n": m.n,
    }
    info.update(extra_info or {})
    return Index(kind, static, arrays, info)


def _build_pgm_index(spec: PGMSpec, table_np: np.ndarray) -> Index:
    return _pgm_to_index(spec.kind, build_pgm(table_np, eps=spec.eps), table_np)


def _build_pgm_m_index(spec: PGMBicriteriaSpec, table_np: np.ndarray) -> Index:
    m = build_pgm_bicriteria(
        table_np, space_budget_bytes=spec.budget_for(len(table_np)), a=spec.a
    )
    return _pgm_to_index(spec.kind, m, table_np, {"a": spec.a})


# -- RadixSpline -------------------------------------------------------------


def _rs_intervals(idx: Index, table, q):
    a = idx.arrays
    n = table.shape[0]
    r_bits = idx.s("r_bits")
    m_valid = a["m_valid"]
    eps_eff = a["eps_eff"]
    qc = jnp.maximum(q, a["kmin"])
    prefix = ((qc - a["kmin"]) >> a["shift"]).astype(POS_DTYPE)
    prefix = jnp.clip(prefix, 0, (1 << r_bits) - 1)
    lo_k = jnp.maximum(jnp.take(a["radix_table"], prefix) - 1, 0)
    hi_k = jnp.take(a["radix_table"], prefix + 1)
    length = jnp.maximum(hi_k - lo_k, 1)
    ub = search.bounded_upper_bound(
        a["knot_keys"], q, lo_k, length, steps=idx.s("ksteps")
    )
    j = jnp.clip(ub - 1, 0, m_valid - 2)
    x1 = jnp.take(a["knot_keys"], j).astype(jnp.float64)
    x2 = jnp.take(a["knot_keys"], j + 1).astype(jnp.float64)
    y1 = jnp.take(a["knot_ranks"], j).astype(jnp.float64)
    y2 = jnp.take(a["knot_ranks"], j + 1).astype(jnp.float64)
    t = (qc.astype(jnp.float64) - x1) / jnp.maximum(x2 - x1, 1.0)
    pred = y1 + jnp.clip(t, 0.0, 1.0) * (y2 - y1)
    lo = jnp.floor(pred).astype(POS_DTYPE) - eps_eff
    hi = jnp.ceil(pred).astype(POS_DTYPE) + eps_eff
    return jnp.clip(lo, 0, n - 1), jnp.clip(hi, 0, n - 1)


def _rs_space(idx: Index) -> int:
    a = idx.arrays
    m = int(np.asarray(a["m_valid"]))
    knots = m * (a["knot_keys"].dtype.itemsize + a["knot_ranks"].dtype.itemsize)
    scalars = a["kmin"].nbytes + a["shift"].nbytes + a["eps_eff"].nbytes + a["m_valid"].nbytes
    return knots + a["radix_table"].nbytes + scalars


def _rs_pallas(idx: Index, table, q):
    """Fused RadixSpline lookup (radix gather + knot search + ε-window
    probe); the f32 re-anchored spline was folded into the Index leaves
    at build time (``rk_*`` arrays).  The radix prefix is query-side
    integer work and is computed here, outside the kernel."""
    from repro.kernels.ops import split_u64
    from repro.kernels.rs_search import fused_rs_search_pallas

    a = idx.arrays
    r_bits = idx.s("r_bits")
    qc = jnp.maximum(q, a["kmin"])
    prefix = jnp.minimum((qc - a["kmin"]) >> a["shift"], jnp.uint64((1 << r_bits) - 1)).astype(
        jnp.int32
    )
    u = jnp.clip((q.astype(jnp.float64) - a["rk_kmin"]) * a["rk_inv_span"], 0.0, 1.0).astype(
        jnp.float32
    )
    qhi, qlo = split_u64(q)
    thi, tlo = split_u64(table)
    khi, klo = split_u64(a["knot_keys"])
    nq = q.shape[0]
    tile = min(512, _pow2ceil(nq))
    u, qhi, qlo, prefix = _pad_queries([u, qhi, qlo, prefix], tile)
    out = fused_rs_search_pallas(
        u,
        qhi,
        qlo,
        prefix,
        thi,
        tlo,
        khi,
        klo,
        a["rk_u0"],
        a["rk_slope"],
        a["knot_ranks"].astype(jnp.int32),
        a["radix_table"].astype(jnp.int32),
        a["m_valid"].reshape(1).astype(jnp.int32),
        a["rk_eps"].reshape(1),
        ksteps=idx.s("ksteps"),
        steps=idx.s("rk_epi"),
        tile_q=tile,
        interpret=kernel_ops._interpret(),
    )
    return out[:nq].astype(POS_DTYPE)


def _rs_pallas_batched(idx: Index, tables, queries):
    """Batched fused RadixSpline lookup: grid over (table, q_tile),
    per-table knot/radix blocks from the stacked arrays.  ``r_bits`` is
    a structural static (stacking requires it to agree), so the radix
    prefix is computed per table outside the kernel exactly as in the
    single-table path."""
    from repro.kernels.ops import split_u64
    from repro.kernels.rs_search import batched_rs_search_pallas

    a = idx.arrays
    r_bits = idx.s("r_bits")
    kmin = a["kmin"][:, None]
    qc = jnp.maximum(queries, kmin)
    prefix = jnp.minimum(
        (qc - kmin) >> a["shift"][:, None], jnp.uint64((1 << r_bits) - 1)
    ).astype(jnp.int32)
    u = jnp.clip(
        (queries.astype(jnp.float64) - a["rk_kmin"][:, None]) * a["rk_inv_span"][:, None],
        0.0,
        1.0,
    ).astype(jnp.float32)
    qhi, qlo = split_u64(queries)
    thi, tlo = split_u64(tables)
    khi, klo = split_u64(a["knot_keys"])
    nq = queries.shape[1]
    tile = min(512, _pow2ceil(nq))
    u, qhi, qlo, prefix = _pad_queries([u, qhi, qlo, prefix], tile, axis=1)
    out = batched_rs_search_pallas(
        u,
        qhi,
        qlo,
        prefix,
        thi,
        tlo,
        khi,
        klo,
        a["rk_u0"],
        a["rk_slope"],
        a["knot_ranks"].astype(jnp.int32),
        a["radix_table"].astype(jnp.int32),
        a["m_valid"].reshape(-1, 1).astype(jnp.int32),
        a["rk_eps"].reshape(-1, 1),
        ksteps=idx.s("ksteps"),
        steps=idx.s("rk_epi"),
        tile_q=tile,
        interpret=kernel_ops._interpret(),
    )
    return out[:, :nq].astype(POS_DTYPE)


RS_IMPL = QueryImpl(
    intervals=_rs_intervals,
    space_bytes=_rs_space,
    pallas=_rs_pallas,
    pallas_batched=_rs_pallas_batched,
)


def rs_model_to_index(kind: str, m, table_np: np.ndarray) -> Index:
    """Wrap an already-fitted :class:`repro.core.radix_spline.RSModel`
    as an Index without refitting (the batched scan-fit path)."""
    from repro.kernels.ops import rs_kernel_arrays

    karr, rksteps = rs_kernel_arrays(m, table_np)
    knot_keys = np.asarray(m.knot_keys)
    knot_ranks = np.asarray(m.knot_ranks)
    arrays = {
        "knot_keys": jnp.asarray(_pad_pow2(knot_keys, _MAXKEY)),
        "knot_ranks": jnp.asarray(_pad_pow2(knot_ranks, knot_ranks[-1])),
        "radix_table": jnp.asarray(m.radix_table),
        "kmin": jnp.asarray(m.kmin).reshape(()),
        "shift": _scalar(m.shift, jnp.uint64),
        "eps_eff": _scalar(m.eps_eff, jnp.int64),
        "m_valid": _scalar(m.m, jnp.int64),
        # fused-kernel re-encoding (query-time cache, not model space)
        "rk_u0": jnp.asarray(_pad_pow2(karr["u0"], np.float32(1.0))),
        "rk_slope": jnp.asarray(_pad_pow2(karr["slope"], np.float32(0.0))),
        "rk_eps": _scalar(karr["eps"], jnp.int32),
        "rk_kmin": _scalar(karr["kmin"], jnp.float64),
        "rk_inv_span": _scalar(karr["inv_span"], jnp.float64),
    }
    static = (
        ("r_bits", m.r_bits),
        ("ksteps", _bucket_steps(_pow2ceil(len(knot_keys)))),
        ("epi", _bucket_steps(min(2 * m.eps_eff + 3, m.n))),
        ("rk_epi", _bucket_steps(1 << rksteps)),
    )
    info = {
        "name": m.name,
        "build_time": m.build_time,
        "eps": m.eps,
        "eps_eff": m.eps_eff,
        "m": m.m,
        "n": m.n,
    }
    return Index(kind, static, arrays, info)


def _build_rs_index(spec: RSSpec, table_np: np.ndarray) -> Index:
    m = build_rs(table_np, eps=spec.eps, r_bits=spec.r_bits)
    return rs_model_to_index(spec.kind, m, table_np)


# -- B+-tree -----------------------------------------------------------------


def _btree_intervals(idx: Index, table, q):
    a = idx.arrays
    n = table.shape[0]
    f = idx.s("fanout")
    levels = idx.s("levels")
    if levels == 0:  # degenerate: table fits one block
        z = jnp.zeros(q.shape, dtype=POS_DTYPE)
        return z, z + (n - 1)
    node = jnp.zeros(q.shape, dtype=POS_DTYPE)
    for lvl in range(levels):
        base = node * f
        fence = a["off"][lvl] + base[..., None] + jnp.arange(f, dtype=POS_DTYPE)
        v = jnp.take(a["keys"], fence, mode="clip")
        child = jnp.sum((v <= q[..., None]).astype(POS_DTYPE), axis=-1)
        child = jnp.maximum(child - 1, 0)
        node = jnp.minimum(base + child, a["valid"][lvl] - 1)
    node = jnp.minimum(node, (n + f - 1) // f - 1)
    lo = node * f
    hi = jnp.minimum(lo + f - 1, n - 1)
    lo = jnp.maximum(lo - 1, 0)
    return lo, hi


def _btree_space(idx: Index) -> int:
    a = idx.arrays
    return a["keys"].nbytes + a["off"].nbytes + a["valid"].nbytes


BTREE_IMPL = QueryImpl(
    intervals=_btree_intervals, space_bytes=_btree_space, pallas=_kary_pallas_fallback
)


def _build_btree_index(spec: BTreeSpec, table_np: np.ndarray) -> Index:
    m = build_btree(table_np, fanout=spec.fanout)
    lvls = [np.asarray(l) for l in m.levels]
    keys = (
        np.concatenate(lvls) if lvls else np.zeros((0,), dtype=np.uint64)
    )
    off = np.concatenate([[0], np.cumsum([len(l) for l in lvls])]).astype(np.int64)
    valid = np.asarray(m.valid, dtype=np.int64)
    arrays = {
        "keys": jnp.asarray(keys),
        "off": jnp.asarray(off),
        "valid": jnp.asarray(valid),
    }
    static = (
        ("fanout", m.fanout),
        ("levels", len(lvls)),
        ("epi", _bucket_steps(min(m.fanout + 1, m.n))),
    )
    info = {"name": m.name, "build_time": m.build_time, "n": m.n}
    return Index(spec.kind, static, arrays, info)


# ---------------------------------------------------------------------------
# Registry wiring — registration order IS the paper's hierarchy order.
# ---------------------------------------------------------------------------

QUERY_IMPLS = {
    "atomic": ATOMIC_IMPL,
    "ko": KO_IMPL,
    "rmi": RMI_IMPL,
    "pgm": PGM_IMPL,
    "rs": RS_IMPL,
    "btree": BTREE_IMPL,
}

_KIND_TO_IMPL = {}


def query_impl(kind: str) -> QueryImpl:
    return QUERY_IMPLS[_KIND_TO_IMPL[kind.upper()]]


def _reg(kind, spec_cls, query_key, build_fn, spec_from_params):
    _KIND_TO_IMPL[kind] = query_key
    register(kind, spec_cls, query_key=query_key, spec_from_params=spec_from_params)(build_fn)


_reg("L", AtomicSpec, "atomic", _build_atomic_index, lambda **p: AtomicSpec(degree=1))
_reg("Q", AtomicSpec, "atomic", _build_atomic_index, lambda **p: AtomicSpec(degree=2))
_reg("C", AtomicSpec, "atomic", _build_atomic_index, lambda **p: AtomicSpec(degree=3))
_reg("KO", KOSpec, "ko", _build_ko_index, lambda **p: KOSpec(k=p.get("k", 15)))
_reg(
    "RMI",
    RMISpec,
    "rmi",
    _build_rmi_index,
    lambda **p: RMISpec(b=p.get("b", 1024), root_type=p.get("root_type", "linear")),
)
_reg(
    "SY-RMI",
    SYRMISpec,
    "rmi",
    _build_sy_rmi_index,
    lambda **p: SYRMISpec(
        space_pct=p.get("space_pct", 2.0),
        ub=p.get("ub", 0.05),
        winner_root=p.get("winner_root", "linear"),
    ),
)
_reg("PGM", PGMSpec, "pgm", _build_pgm_index, lambda **p: PGMSpec(eps=p.get("eps", 64)))
_reg(
    "PGM_M",
    PGMBicriteriaSpec,
    "pgm",
    _build_pgm_m_index,
    lambda **p: PGMBicriteriaSpec(
        space_budget_bytes=p.get("space_budget_bytes", 0),
        space_pct=p.get("space_pct", 2.0),
        a=p.get("a", 1.0),
    ),
)
_reg(
    "RS",
    RSSpec,
    "rs",
    _build_rs_index,
    lambda **p: RSSpec(eps=p.get("eps", 32), r_bits=p.get("r_bits", 12)),
)
_reg(
    "BTREE",
    BTreeSpec,
    "btree",
    _build_btree_index,
    lambda **p: BTreeSpec(fanout=p.get("fanout", 16)),
)
