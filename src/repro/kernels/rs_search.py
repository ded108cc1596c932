"""Fused RadixSpline lookup — radix-table gather + spline-knot search +
error-window probe, one Pallas kernel.

The RadixSpline query (paper §3.2) is three dependent stages: a radix
table over the top ``r`` bits narrows the knot range, a bounded search
finds the enclosing knot pair, and linear interpolation between the
knots predicts an ε-window over the table.  The XLA path runs these as
separate gathers through :mod:`repro.index.impls`; here they fuse onto
one resident query tile, including the final ε-window probe (the
"radix-table gather + knot search fuses cleanly" item from ROADMAP).

TPU adaptations, mirroring :mod:`rmi_search` / :mod:`pgm_search`:

* the radix prefix ``(q - kmin) >> shift`` is pure query-side integer
  work, pre-computed outside the kernel in native u64 (no limb shifts
  in-kernel);
* knot selection is the exact limb-compare bounded search, so the knot
  pair is **exact**; only the interpolation is approximate;
* interpolation is re-anchored in f32 ``u`` space: ``pred = y1 +
  slope_j * (u - u1)`` with per-knot-segment slopes precomputed at
  build (:func:`repro.kernels.ops.rs_kernel_arrays`), which re-measures
  the prediction error of every table key *and every knot boundary*
  with exactly this f32 arithmetic and widens ε so the window stays a
  guarantee (f32 rounding is monotone between knots).

Two entry points share one kernel body: :func:`fused_rs_search_pallas`
(single table, grid over query tiles) and
:func:`batched_rs_search_pallas` (a tier/batch of tables, grid over
``(table, q_tile)`` with per-table knot/radix blocks — the pattern
:mod:`rmi_search` established), the latter backing
``BatchedIndexes.lookup(backend="pallas")`` and the sharded tier's
vmapped fallback for the RS kind.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pgm_search import _bounded_ub_limbs
from .rmi_search import _F32_HI, _F32_LO, _ONE, _ZERO, DEFAULT_TILE_Q


def _rs_body(
    u,
    qhi,
    qlo,
    prefix,
    thi,
    tlo,
    khi,
    klo,
    u0_a,
    slope_a,
    rank_a,
    radix,
    m_valid,
    eps,
    *,
    n: int,
    ksteps: int,
    steps: int,
):
    """The fused three-stage lookup on plain arrays."""
    # --- stage 1: radix-table gather bounds the knot range ---
    lo_k = jnp.maximum(jnp.take(radix, prefix) - _ONE, _ZERO)
    hi_k = jnp.take(radix, prefix + _ONE)
    length = jnp.maximum(hi_k - lo_k, _ONE)

    # --- stage 2: exact knot search (limb compare) + f32 interpolation ---
    ub = _bounded_ub_limbs(khi, klo, qhi, qlo, lo_k, length, steps=ksteps)
    j = jnp.clip(ub - _ONE, _ZERO, m_valid - np.int32(2))
    y1 = jnp.take(rank_a, j).astype(jnp.float32)
    pred = y1 + jnp.take(slope_a, j) * jnp.maximum(u - jnp.take(u0_a, j), np.float32(0.0))
    pred = jnp.clip(pred, _F32_LO, _F32_HI)
    # clamp the predicted CENTER into the table before widening (see
    # pgm_search: an f32 u-resolution collapse can push pred far past
    # the table and collapse the ±ε window to the last slot; the true
    # rank is always in [0, n-1], so clamping the center is sound).
    last = np.int32(n - 1)
    p_lo = jnp.clip(jnp.floor(pred).astype(jnp.int32), _ZERO, last)
    p_hi = jnp.clip(jnp.ceil(pred).astype(jnp.int32), _ZERO, last)
    lo = jnp.clip(p_lo - eps, _ZERO, last)
    hi = jnp.clip(p_hi + eps, _ZERO, last)

    # --- stage 3: ε-window probe over the table limbs ---
    ub_t = _bounded_ub_limbs(thi, tlo, qhi, qlo, lo, hi - lo + _ONE, steps=steps)
    return ub_t - _ONE


def _rs_kernel(
    u_ref,
    qhi_ref,
    qlo_ref,
    prefix_ref,
    thi_ref,
    tlo_ref,
    khi_ref,
    klo_ref,
    u0_ref,
    slope_ref,
    rank_ref,
    radix_ref,
    mv_ref,
    eps_ref,
    out_ref,
    *,
    n: int,
    ksteps: int,
    steps: int,
):
    out_ref[...] = _rs_body(
        u_ref[...],
        qhi_ref[...],
        qlo_ref[...],
        prefix_ref[...],
        thi_ref[...],
        tlo_ref[...],
        khi_ref[...],
        klo_ref[...],
        u0_ref[...],
        slope_ref[...],
        rank_ref[...],
        radix_ref[...],
        mv_ref[0],
        eps_ref[0],
        n=n,
        ksteps=ksteps,
        steps=steps,
    )


def fused_rs_search_pallas(
    u_f32,
    q_hi,
    q_lo,
    prefix_i32,
    table_hi,
    table_lo,
    knot_hi,
    knot_lo,
    rk_u0,
    rk_slope,
    knot_rank_i32,
    radix_i32,
    m_valid_i32,
    eps_i32,
    *,
    ksteps: int,
    steps: int,
    tile_q: int = DEFAULT_TILE_Q,
    interpret: bool = True,
):
    """pallas_call wrapper for the fused RadixSpline lookup.

    ``prefix_i32`` is the per-query radix prefix (pre-computed outside,
    clipped to ``[0, 2^r - 1]``); ``knot_hi/lo`` the limb split of the
    padded knot keys; ``rk_u0``/``rk_slope`` the f32 re-anchored spline
    (:func:`repro.kernels.ops.rs_kernel_arrays`); ``m_valid_i32`` /
    ``eps_i32`` one-element arrays with the valid knot count and the
    f32-widened ε.  Queries must be padded to a tile multiple.
    """
    nq = u_f32.shape[0]
    n = table_hi.shape[0]
    mk = knot_hi.shape[0]
    rn = radix_i32.shape[0]
    assert nq % tile_q == 0, "pad queries to a tile multiple (see ops.py)"
    grid = (nq // tile_q,)

    def qspec():
        return pl.BlockSpec((tile_q,), lambda i: (i,))

    def full(m):
        return pl.BlockSpec((m,), lambda i: (0,))

    kernel = functools.partial(_rs_kernel, n=n, ksteps=ksteps, steps=steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            qspec(),  # u
            qspec(),  # q_hi
            qspec(),  # q_lo
            qspec(),  # prefix
            full(n),  # table_hi
            full(n),  # table_lo
            full(mk),  # knot_hi
            full(mk),  # knot_lo
            full(mk),  # rk_u0
            full(mk),  # rk_slope
            full(mk),  # knot ranks
            full(rn),  # radix table
            full(1),  # m_valid
            full(1),  # eps
        ],
        out_specs=qspec(),
        out_shape=jax.ShapeDtypeStruct((nq,), jnp.int32),
        interpret=interpret,
    )(
        u_f32,
        q_hi,
        q_lo,
        prefix_i32,
        table_hi,
        table_lo,
        knot_hi,
        knot_lo,
        rk_u0,
        rk_slope,
        knot_rank_i32,
        radix_i32,
        m_valid_i32,
        eps_i32,
    )


def _rs_kernel_batched(
    u_ref,
    qhi_ref,
    qlo_ref,
    prefix_ref,
    thi_ref,
    tlo_ref,
    khi_ref,
    klo_ref,
    u0_ref,
    slope_ref,
    rank_ref,
    radix_ref,
    mv_ref,
    eps_ref,
    out_ref,
    *,
    n: int,
    ksteps: int,
    steps: int,
):
    # leading table axis of extent 1 per block: squeeze and reuse the
    # single-table body verbatim (the rmi_search pattern)
    out_ref[0, :] = _rs_body(
        u_ref[0],
        qhi_ref[0],
        qlo_ref[0],
        prefix_ref[0],
        thi_ref[0],
        tlo_ref[0],
        khi_ref[0],
        klo_ref[0],
        u0_ref[0],
        slope_ref[0],
        rank_ref[0],
        radix_ref[0],
        mv_ref[0, 0],
        eps_ref[0, 0],
        n=n,
        ksteps=ksteps,
        steps=steps,
    )


def batched_rs_search_pallas(
    u_f32,
    q_hi,
    q_lo,
    prefix_i32,
    table_hi,
    table_lo,
    knot_hi,
    knot_lo,
    rk_u0,
    rk_slope,
    knot_rank_i32,
    radix_i32,
    m_valid_i32,
    eps_i32,
    *,
    ksteps: int,
    steps: int,
    tile_q: int = DEFAULT_TILE_Q,
    interpret: bool = True,
):
    """Batched/tier variant of the fused RadixSpline lookup:
    ``(n_tables, nq)`` queries against ``(n_tables, n)`` tables with
    per-table knot/radix blocks.

    Grid is ``(table, q_tile)``; each program gets its table's knot
    limbs, spline re-encoding, radix table, valid-knot count and
    ε (leading axis extent 1) plus one query tile, so ONE
    ``pallas_call`` answers a whole batch/tier.  ``r_bits`` is a
    structural static (stacking requires it to agree across tables), so
    every radix block has the same length; ``ksteps``/``steps`` must
    cover the widest per-table knot range / window (max-merged at stack
    time — extra fixed-trip iterations are no-ops).
    """
    nt, nq = u_f32.shape
    n = table_hi.shape[1]
    mk = knot_hi.shape[1]
    rn = radix_i32.shape[1]
    assert nq % tile_q == 0, "pad queries to a tile multiple (see ops.py)"
    grid = (nt, nq // tile_q)

    def qspec():
        return pl.BlockSpec((1, tile_q), lambda t, i: (t, i))

    def per_table(m):
        return pl.BlockSpec((1, m), lambda t, i: (t, 0))

    kernel = functools.partial(_rs_kernel_batched, n=n, ksteps=ksteps, steps=steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            qspec(),  # u
            qspec(),  # q_hi
            qspec(),  # q_lo
            qspec(),  # prefix
            per_table(n),  # table_hi
            per_table(n),  # table_lo
            per_table(mk),  # knot_hi
            per_table(mk),  # knot_lo
            per_table(mk),  # rk_u0
            per_table(mk),  # rk_slope
            per_table(mk),  # knot ranks
            per_table(rn),  # radix table
            per_table(1),  # m_valid
            per_table(1),  # eps
        ],
        out_specs=qspec(),
        out_shape=jax.ShapeDtypeStruct((nt, nq), jnp.int32),
        interpret=interpret,
    )(
        u_f32,
        q_hi,
        q_lo,
        prefix_i32,
        table_hi,
        table_lo,
        knot_hi,
        knot_lo,
        rk_u0,
        rk_slope,
        knot_rank_i32,
        radix_i32,
        m_valid_i32,
        eps_i32,
    )
