"""Public jit'd wrappers around the Pallas kernels.

Handles host-side preparation (u32 limb split, f32 pre-normalisation,
query padding, f32-widened error bounds) and falls back to interpret
mode off-TPU.  ``ref.py`` holds the oracles; tests sweep shapes/dtypes.

The ``*_kernel_arrays`` re-encoders here serve both the single-table
fused kernels and their batched ``(table, q_tile)``-grid variants
(``batched_rmi_search_pallas`` / ``batched_pgm_search_pallas`` /
``batched_rs_search_pallas``): the re-encoded leaves stack leaf-wise
like the model arrays, and the bucketed trip-count statics merge by max
at stack time, so one re-encoding per table covers every dispatch path.
"""

from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.cdf import ceil_log2
from repro.core.limbs import LimbTable

from .rmi_search import DEFAULT_TILE_Q
from .kary_search import kary_search_pallas, LANES
from .embedding_bag import embedding_bag_pallas
from .decode_attention import decode_attention_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def split_u64(x_u64: np.ndarray):
    """uint64 -> (hi, lo) uint32 limbs (host or device arrays).  A
    :class:`~repro.core.limbs.LimbTable` is split already: its planes
    are returned as they are."""
    if isinstance(x_u64, LimbTable):
        return x_u64.hi, x_u64.lo
    x = jnp.asarray(x_u64, dtype=jnp.uint64)
    hi = (x >> jnp.uint64(32)).astype(jnp.uint32)
    lo = (x & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    return hi, lo


def _pad_to(x, mult, fill):
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    return jnp.concatenate([x, jnp.full((pad,), fill, dtype=x.dtype)]), n


# ---------------------------------------------------------------------------
# Fused RMI search
# ---------------------------------------------------------------------------


def rmi_kernel_arrays(model, table_np: np.ndarray):
    """Re-encode a core.rmi.RMIModel in kernel precision, re-verifying ε.

    The kernel predicts in f32; we re-measure every leaf's max error with
    the kernel's exact arithmetic (f32 Horner on f32 u) and widen ε so
    the window remains a guarantee.  Returns ``(arrays, steps)`` where
    ``arrays`` holds the f32/i32 leaf parameters (``root``, ``slope``,
    ``icept``, ``eps``, ``rlo``, ``rhi``) — this is what
    :class:`repro.index.Index` folds into its pytree leaves at build
    time; ``Index.lookup(..., backend="pallas")`` runs the fused kernel.
    """
    n = model.n
    b = model.b
    kmin = np.float64(np.asarray(model.kmin))
    inv_span = np.float64(np.asarray(model.inv_span))

    u64 = (table_np.astype(np.float64) - kmin) * inv_span
    u32 = np.clip(u64, 0.0, 1.0).astype(np.float32)

    root = np.asarray(model.root_coef, dtype=np.float32)
    slopes = np.asarray(model.leaf_slope, dtype=np.float32)
    icepts = np.asarray(model.leaf_icept, dtype=np.float32)

    # leaf assignment with kernel arithmetic (f32)
    p_root = ((root[3] * u32 + root[2]) * u32 + root[1]) * u32 + root[0]
    leaf = np.clip(np.floor(p_root.astype(np.float64) * (b / n)), 0, b - 1).astype(np.int64)
    leaf = np.maximum.accumulate(leaf)
    r32 = np.searchsorted(leaf, np.arange(b + 1), side="left").astype(np.int64)

    # f32 leaf prediction error at every key (exactly the kernel math)
    pred = slopes[leaf] * u32 + icepts[leaf]
    ranks = np.arange(n, dtype=np.float64)
    err = np.abs(pred.astype(np.float64) - ranks)
    eps = np.zeros(b)
    np.maximum.at(eps, leaf, err)
    # extended boundary keys per leaf (guarantee argument, DESIGN.md §3)
    lo_idx = np.clip(r32[:-1] - 1, 0, n - 1)
    hi_idx = np.clip(r32[1:], 0, n - 1)
    err_lo = np.abs(slopes * u32[lo_idx] + icepts - ranks[lo_idx])
    err_hi = np.abs(slopes * u32[hi_idx] + icepts - ranks[hi_idx])
    eps = np.maximum(eps, np.maximum(err_lo, err_hi))
    eps_i = np.minimum(np.ceil(eps) + 2, float(n)).astype(np.int32)

    rlo = np.maximum(r32[:-1] - 1, 0).astype(np.int32)
    # high fence r32[l+1] (not -1): absorbs a 1-ulp leaf flip between the
    # host re-encoding and the kernel's f32 root eval (err_hi covers the
    # boundary key, so the widened window stays a guarantee).
    rhi = np.clip(r32[1:], 0, n - 1).astype(np.int32)
    widths = np.minimum(2 * eps_i.astype(np.int64) + 3, (rhi - rlo + 1).astype(np.int64))
    max_window = max(1, int(widths.max()))
    steps = max(1, int(math.ceil(math.log2(max(max_window, 2)))))

    arrays = {"root": root, "slope": slopes, "icept": icepts, "eps": eps_i, "rlo": rlo, "rhi": rhi}
    return arrays, steps


def pgm_kernel_arrays(model, table_np: np.ndarray):
    """Re-encode a :class:`repro.core.pgm.PGMModel` for the fused Pallas
    descent (:mod:`repro.kernels.pgm_search`), re-verifying ε.

    The kernel predicts per segment in f32 ``u`` space, anchored at the
    segment's own coordinate: ``pred = r0 + slope_u * max(u - u0, 0)``
    with ``slope_u = slope * span``.  This function re-measures every
    level's prediction error *with exactly that arithmetic* at every
    child entry (exact segment assignment — routing in the kernel is an
    exact limb-compare search) and widens ε so the window remains a
    guarantee; f32 rounding is monotone, so queries between keys stay
    covered, and the level fence clamp absorbs gap extrapolation just
    like the f64 path.

    Returns ``(arrays, steps)``: ``arrays`` holds the level-concatenated
    f32 leaves (``u0``, ``slope``) plus the scalar ``eps`` / ``kmin`` /
    ``inv_span``; ``steps`` is the unbucketed trip count for every
    in-kernel bounded search.  :mod:`repro.index.impls` folds these into
    the Index pytree as the ``pk_*`` leaves at build time, exactly as
    :func:`rmi_kernel_arrays` does for the RMI family.

    Example::

        m = build_pgm(table, eps=32)
        arrays, steps = pgm_kernel_arrays(m, table)
        assert arrays["u0"].shape[0] == sum(m.level_sizes)
    """
    n = model.n
    kmin = np.float64(table_np[0])
    span = np.float64(table_np[-1]) - kmin
    inv_span = np.float64(1.0) / span if span > 0 else np.float64(1.0)

    def u_of(keys_u64):
        u = (keys_u64.astype(np.float64) - kmin) * inv_span
        return np.clip(u, 0.0, 1.0).astype(np.float32)

    levels = len(model.level_keys)
    u0_parts, slope_parts = [], []
    max_err = 0.0
    for lvl in range(levels):
        keys_l = np.asarray(model.level_keys[lvl])
        u0_l = u_of(keys_l)
        slope_u = (np.asarray(model.level_slope[lvl]) * span).astype(np.float32)
        u0_parts.append(u0_l)
        slope_parts.append(slope_u)
        child = np.asarray(model.level_keys[lvl + 1]) if lvl + 1 < levels else table_np
        # exact segment assignment — mirrors the kernel's limb-compare route
        s = np.clip(np.searchsorted(keys_l, child, side="right") - 1, 0, len(keys_l) - 1)
        r0 = np.asarray(model.level_rank0[lvl])[s].astype(np.float32)
        du = np.maximum(u_of(child) - u0_l[s], np.float32(0.0))
        pred = r0 + slope_u[s] * du  # the kernel's f32 arithmetic, verbatim
        err = np.abs(pred.astype(np.float64) - np.arange(len(child), dtype=np.float64))
        if len(err):
            max_err = max(max_err, float(err.max()))
    # +2: one for between-keys interpolation drift beyond the widened ±1
    # the query path already adds, one for XLA fusing mul+add into an FMA
    eps = int(min(np.ceil(max_err) + 2, n))
    steps = ceil_log2(min(2 * (eps + 1) + 3, max(n, 2)))
    arrays = {
        "u0": np.concatenate(u0_parts),
        "slope": np.concatenate(slope_parts),
        "eps": eps,
        "kmin": kmin,
        "inv_span": inv_span,
    }
    return arrays, steps


def pgm_level_reencode_device(keys_l, slopes_l, start_l, nseg, child, child_count, kmin, span, inv_span):
    """Device (jittable) counterpart of ONE level of
    :func:`pgm_kernel_arrays`: re-encode a PGM level in the fused
    kernel's f32 anchored arithmetic and re-measure its prediction error
    at every *valid* child entry.

    Arrays are fixed-capacity with traced live counts: ``keys_l`` /
    ``slopes_l`` / ``start_l`` hold ``nseg`` valid segments (key pads
    are the max-key sentinel, so the segment route stays exact — see
    :func:`pgm_kernel_arrays` for the host-side arithmetic this
    replicates operation-for-operation), and ``child`` holds
    ``child_count`` valid entries whose errors count toward the bound.

    Returns ``(u0_l, slope_u, max_err)``; the caller accumulates the
    per-level errors into the widened ``pk_eps`` exactly as the host
    re-encoder does.

    Example::

        u0, su, err = pgm_level_reencode_device(
            lvl_keys, lvl_slopes, lvl_starts, nseg,
            child_keys, child_count, kmin, span, inv_span)
    """

    def u_of(keys_u64):
        u = (keys_u64.astype(jnp.float64) - kmin) * inv_span
        return jnp.clip(u, 0.0, 1.0).astype(jnp.float32)

    u0_l = u_of(keys_l)
    slope_u = (slopes_l * span).astype(jnp.float32)
    # exact segment assignment — max-key pads sort above every real child
    s = jnp.clip(
        jnp.searchsorted(keys_l, child, side="right") - 1, 0, jnp.maximum(nseg - 1, 0)
    )
    r0 = jnp.take(start_l, s).astype(jnp.float32)
    du = jnp.maximum(u_of(child) - jnp.take(u0_l, s), jnp.float32(0.0))
    pred = r0 + jnp.take(slope_u, s) * du  # the kernel's f32 arithmetic
    cap = child.shape[0]
    err = jnp.abs(pred.astype(jnp.float64) - jnp.arange(cap, dtype=jnp.float64))
    err = jnp.where(jnp.arange(cap) < child_count, err, 0.0)
    return u0_l, slope_u, jnp.max(err)


def rs_kernel_arrays_device(knot_keys, knot_ranks, m_valid, table_row, kmin, span, inv_span):
    """Device (jittable) counterpart of :func:`rs_kernel_arrays`:
    re-encode a RadixSpline knot set in the fused kernel's f32 anchored
    arithmetic and re-measure ε with that exact arithmetic.

    ``knot_keys`` / ``knot_ranks`` are fixed-capacity rows with
    ``m_valid`` live knots (max-key / edge sentinels beyond); every key
    of ``table_row`` is treated as valid (device refreshes fit on the
    padded capacity table, so ``n == table_row.shape[0]``).

    Returns ``(u0, slope, rk_eps)`` with ``rk_eps`` the widened i32
    bound — same ``ceil(max_err) + 2`` margin as the host re-encoder.

    Example::

        u0, sl, rk_eps = rs_kernel_arrays_device(
            kk, kr, m_valid, padded_tab, kmin, span, inv_span)
    """
    n = table_row.shape[0]
    cap = knot_keys.shape[0]

    def u_of(keys_u64):
        u = (keys_u64.astype(jnp.float64) - kmin) * inv_span
        return jnp.clip(u, 0.0, 1.0).astype(jnp.float32)

    u0 = u_of(knot_keys)
    i = jnp.arange(cap)
    nxt = jnp.minimum(i + 1, cap - 1)
    dy = (jnp.take(knot_ranks, nxt) - knot_ranks).astype(jnp.float32)
    du = jnp.take(u0, nxt) - u0
    valid_pair = (i + 1) < m_valid
    # u-collided knot pairs (f32 resolution) predict y1 flat, like host
    slope = jnp.where(valid_pair & (du > 0), dy / jnp.where(du > 0, du, 1.0), 0.0).astype(
        jnp.float32
    )
    j = jnp.clip(
        jnp.searchsorted(knot_keys, table_row, side="right") - 1,
        0,
        jnp.maximum(m_valid - 2, 0),
    )
    y1 = jnp.take(knot_ranks, j).astype(jnp.float32)
    pred = y1 + jnp.take(slope, j) * jnp.maximum(
        u_of(table_row) - jnp.take(u0, j), jnp.float32(0.0)
    )
    err = jnp.abs(pred.astype(jnp.float64) - jnp.arange(n, dtype=jnp.float64))
    # boundary extension: each knot under its left segment's model
    pred_b = knot_ranks.astype(jnp.float32) + slope * jnp.maximum(du, jnp.float32(0.0))
    err_b = jnp.abs(pred_b.astype(jnp.float64) - jnp.take(knot_ranks, nxt).astype(jnp.float64))
    err_b = jnp.where(valid_pair, err_b, 0.0)
    max_err = jnp.maximum(jnp.max(err), jnp.max(err_b))
    rk_eps = jnp.minimum(jnp.ceil(max_err) + 2.0, float(n)).astype(jnp.int32)
    return u0, slope, rk_eps


def rs_kernel_arrays(model, table_np: np.ndarray):
    """Re-encode a :class:`repro.core.radix_spline.RSModel` for the fused
    Pallas lookup (:mod:`repro.kernels.rs_search`), re-verifying ε.

    Interpolation between knots is re-anchored in f32 ``u`` space with a
    precomputed per-knot-segment slope: ``pred = y1 + slope_j *
    max(u - u1, 0)``.  The error of that exact arithmetic is re-measured
    at every table key *and* at every knot evaluated under its left
    neighbour's segment (the boundary a query can reach just below a
    knot), and ε widens accordingly, so the reported window stays a
    guarantee under f32 rounding (which is monotone between knots).

    Returns ``(arrays, steps)``: f32 ``u0``/``slope`` per knot plus the
    scalar ``eps``/``kmin``/``inv_span``, and the unbucketed trip count
    of the final window probe.  Folded into the Index as ``rk_*`` leaves
    at build time.

    Example::

        m = build_rs(table, eps=32, r_bits=10)
        arrays, steps = rs_kernel_arrays(m, table)
        assert arrays["u0"].shape[0] == m.m
    """
    n = model.n
    m = model.m
    knot_keys = np.asarray(model.knot_keys)[:m]
    knot_ranks = np.asarray(model.knot_ranks)[:m]
    kmin = np.float64(np.asarray(model.kmin))
    span = np.float64(table_np[-1]) - kmin
    inv_span = np.float64(1.0) / span if span > 0 else np.float64(1.0)

    def u_of(keys_u64):
        u = (keys_u64.astype(np.float64) - kmin) * inv_span
        return np.clip(u, 0.0, 1.0).astype(np.float32)

    u0 = u_of(knot_keys)
    slope = np.zeros(m, dtype=np.float32)
    if m >= 2:
        dy = (knot_ranks[1:] - knot_ranks[:-1]).astype(np.float32)
        du = u0[1:] - u0[:-1]
        # u-collided knot pairs (f32 resolution) predict y1 flat; the
        # measured ε absorbs the rank span they cover
        np.divide(dy, du, out=slope[:-1], where=du > 0)
        j = np.clip(np.searchsorted(knot_keys, table_np, side="right") - 1, 0, m - 2)
        y1 = knot_ranks[j].astype(np.float32)
        pred = y1 + slope[j] * np.maximum(u_of(table_np) - u0[j], np.float32(0.0))
        err = np.abs(pred.astype(np.float64) - np.arange(n, dtype=np.float64))
        # boundary extension: each knot under its left segment's model
        pred_b = knot_ranks[:-1].astype(np.float32) + slope[:-1] * np.maximum(du, np.float32(0.0))
        err_b = np.abs(pred_b.astype(np.float64) - knot_ranks[1:].astype(np.float64))
        max_err = max(float(err.max()), float(err_b.max()))
        eps = int(min(np.ceil(max_err) + 2, n))
    else:
        eps = max(int(n), 1)
    steps = ceil_log2(min(2 * eps + 3, max(n, 2)))
    arrays = {"u0": u0, "slope": slope, "eps": eps, "kmin": kmin, "inv_span": inv_span}
    return arrays, steps


# ---------------------------------------------------------------------------
# Lane-wide k-ary search
# ---------------------------------------------------------------------------


def kary_search(table_u64, queries_u64, *, k: int = LANES, tile_q: int = DEFAULT_TILE_Q):
    thi, tlo = split_u64(table_u64)
    qhi, qlo = split_u64(queries_u64)
    qhi, nq = _pad_to(qhi, tile_q, 0)
    qlo, _ = _pad_to(qlo, tile_q, 0)
    out = kary_search_pallas(qhi, qlo, thi, tlo, k=k, tile_q=tile_q, interpret=_interpret())
    return out[:nq]


# ---------------------------------------------------------------------------
# EmbeddingBag
# ---------------------------------------------------------------------------


def embedding_bag(table, ids, seg_ids, weights=None, *, num_bags: int, v_tile: int = 512):
    table = jnp.asarray(table, jnp.float32)
    v, d = table.shape
    pad_v = (-v) % v_tile
    if pad_v:
        table = jnp.concatenate([table, jnp.zeros((pad_v, d), jnp.float32)])
    ids = jnp.asarray(ids, jnp.int32)
    seg_ids = jnp.asarray(seg_ids, jnp.int32)
    if weights is None:
        weights = jnp.ones(ids.shape, jnp.float32)
    return embedding_bag_pallas(
        table, ids, seg_ids, jnp.asarray(weights, jnp.float32),
        num_bags=num_bags, v_tile=v_tile, interpret=_interpret(),
    )


# ---------------------------------------------------------------------------
# Flash-decode attention
# ---------------------------------------------------------------------------


def decode_attention(q, k, v, kv_len, *, s_tile: int = 256):
    q = jnp.asarray(q, jnp.float32)
    k = jnp.asarray(k, jnp.float32)
    v = jnp.asarray(v, jnp.float32)
    b, s, hkv, d = k.shape
    pad_s = (-s) % s_tile
    if pad_s:
        zk = jnp.zeros((b, pad_s, hkv, d), jnp.float32)
        k = jnp.concatenate([k, zk], axis=1)
        v = jnp.concatenate([v, zk], axis=1)
    return decode_attention_pallas(
        q, k, v, jnp.asarray(kv_len, jnp.int32), s_tile=s_tile, interpret=_interpret()
    )
