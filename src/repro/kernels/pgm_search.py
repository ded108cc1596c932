"""Fused PGM descent — root route + per-level segment gather + ε-window
bounded search, one Pallas kernel.

The PGM query (paper §3.2) is a top-down walk: at each level, the
current segment's linear model predicts a window over the level below,
and an exact bounded search of that window yields the next level's
segment.  The XLA path in :mod:`repro.index.impls` unrolls this as one
``jnp`` stage per level; this kernel fuses the whole descent so every
level's gather + predict + search happens on the same resident query
tile (the paper's "tight search kernel" requirement for learned models
to beat binary search).

TPU adaptations, mirroring :mod:`rmi_search`:

* keys travel as u32 limb pairs; every search compare is the
  lexicographic limb compare (exact, so **routing is exact** — only the
  predictions are approximate);
* per-segment predictions are re-anchored into the f32 CDF coordinate
  ``u`` pre-normalised outside the kernel: ``pred = r0 + slope_u *
  max(u - u0, 0)`` with ``slope_u = slope * span``.  Anchoring at the
  segment's own ``u0`` keeps the multiplicand small (Sterbenz regime),
  so cancellation cannot blow the window;
* the build re-measures every level's prediction error with exactly
  this f32 arithmetic and widens ε accordingly
  (:func:`repro.kernels.ops.pgm_kernel_arrays`); f32 rounding is
  monotone, so the widened window stays a guarantee for queries between
  keys.  The predicted *center* is clamped into the exact
  ``[r0-1, r1-1]`` fence range before the ±ε widening, so
  gap-extrapolation and u-resolution blow-ups degrade to a full-segment
  window instead of collapsing it to one fence slot;
* the level directories (``off``/``off_r``/``sizes``) are tiny i32
  arrays indexed by the *static* level counter, so the level loop fully
  unrolls with static offsets into the flat padded leaf arrays —
  the same padded-leaf encoding ``_lift_pgm_levels`` produces for
  shard-stacking, which is what makes this kernel tier-stackable.

Two entry points share one kernel body: :func:`fused_pgm_search_pallas`
(single table, grid over query tiles) and
:func:`batched_pgm_search_pallas` (a tier/batch of level-harmonised
tables, grid over ``(table, q_tile)`` with per-table parameter blocks —
the pattern :mod:`rmi_search` established), the latter backing
``BatchedIndexes.lookup(backend="pallas")`` and the sharded tier's
vmapped fallback for the PGM family.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .rmi_search import _F32_HI, _F32_LO, _le_u64, _ONE, _ZERO, DEFAULT_TILE_Q


def _bounded_ub_limbs(khi, klo, qhi, qlo, base, length, *, steps: int):
    """First index in [base, base+length) with key > q (limb compare);
    ``base + length`` if none.  Fixed-trip Khuong–Morin loop."""

    def body(_, carry):
        b, n = carry
        half = n >> _ONE
        mid = b + half
        go_right = _le_u64(jnp.take(khi, mid), jnp.take(klo, mid), qhi, qlo) & (n > _ONE)
        b = jnp.where(go_right, mid, b)
        n = n - jnp.where(n > _ONE, half, _ZERO)
        return b, n

    b, _ = lax.fori_loop(0, steps, body, (base, length))
    le = _le_u64(jnp.take(khi, b), jnp.take(klo, b), qhi, qlo)
    return b + le.astype(jnp.int32)


def _pgm_body(
    u,
    qhi,
    qlo,
    thi,
    tlo,
    khi,
    klo,
    u0_a,
    slope_a,
    r0_a,
    off,
    off_r,
    sizes,
    eps,
    *,
    levels: int,
    n: int,
    steps: int,
):
    """The fused descent on plain arrays (shared single/batched body)."""
    seg = jnp.zeros(u.shape, dtype=jnp.int32)
    for lvl in range(levels):  # static unroll: off[lvl] reads are scalar
        base_k = off[lvl]
        base_r = off_r[lvl]
        u0 = jnp.take(u0_a, base_k + seg)
        slope = jnp.take(slope_a, base_k + seg)
        r0 = jnp.take(r0_a, base_r + seg)
        r1 = jnp.take(r0_a, base_r + seg + _ONE)
        pred = r0.astype(jnp.float32) + slope * jnp.maximum(u - u0, np.float32(0.0))
        pred = jnp.clip(pred, _F32_LO, _F32_HI)  # gap blow-ups: clamp pre-cast
        b_lo = jnp.maximum(r0 - _ONE, _ZERO)
        b_hi = r1 - _ONE
        # clamp the predicted CENTER into the fence range before widening:
        # an f32 u-resolution collapse (dense cluster inside a huge key
        # span) can push pred thousands of ranks past the segment, and
        # ±(ε+1) around the raw pred would collapse the clipped window to
        # a single fence slot.  The true rank always lies in
        # [b_lo, b_hi], so clamping the center never increases
        # |center - true| and the measured-ε guarantee survives.
        p_lo = jnp.clip(jnp.floor(pred).astype(jnp.int32), b_lo, b_hi)
        p_hi = jnp.clip(jnp.ceil(pred).astype(jnp.int32), b_lo, b_hi)
        lo = jnp.clip(p_lo - (eps + _ONE), b_lo, b_hi)
        hi = jnp.clip(p_hi + (eps + _ONE), b_lo, b_hi)
        if lvl + 1 < levels:
            base_n = off[lvl + 1]
            ub = _bounded_ub_limbs(khi, klo, qhi, qlo, base_n + lo, hi - lo + _ONE, steps=steps)
            seg = jnp.clip(ub - base_n - _ONE, _ZERO, sizes[lvl + 1] - _ONE)
        else:
            # leaf level: r0 indexes the table — final ε-window search
            last = np.int32(n - 1)
            lo = jnp.clip(lo, _ZERO, last)
            hi = jnp.clip(hi, _ZERO, last)
            ub = _bounded_ub_limbs(thi, tlo, qhi, qlo, lo, hi - lo + _ONE, steps=steps)
            return ub - _ONE
    raise AssertionError("unreachable")


def _pgm_kernel(
    u_ref,
    qhi_ref,
    qlo_ref,
    thi_ref,
    tlo_ref,
    khi_ref,
    klo_ref,
    u0_ref,
    slope_ref,
    r0_ref,
    off_ref,
    off_r_ref,
    sizes_ref,
    eps_ref,
    out_ref,
    *,
    levels: int,
    n: int,
    steps: int,
):
    out_ref[...] = _pgm_body(
        u_ref[...],
        qhi_ref[...],
        qlo_ref[...],
        thi_ref[...],
        tlo_ref[...],
        khi_ref[...],
        klo_ref[...],
        u0_ref[...],
        slope_ref[...],
        r0_ref[...],
        off_ref[...],
        off_r_ref[...],
        sizes_ref[...],
        eps_ref[0],
        levels=levels,
        n=n,
        steps=steps,
    )


def fused_pgm_search_pallas(
    u_f32,
    q_hi,
    q_lo,
    table_hi,
    table_lo,
    keys_hi,
    keys_lo,
    pk_u0,
    pk_slope,
    rank0_i32,
    off_i32,
    off_r_i32,
    sizes_i32,
    eps_i32,
    *,
    levels: int,
    steps: int,
    tile_q: int = DEFAULT_TILE_Q,
    interpret: bool = True,
):
    """pallas_call wrapper for the fused PGM descent.

    ``keys_hi/lo`` are the limb split of the level-concatenated padded
    segment keys; ``pk_u0``/``pk_slope`` the f32 re-anchored segment
    models (:func:`repro.kernels.ops.pgm_kernel_arrays`); ``rank0_i32``
    the concatenated level directories; ``eps_i32`` a one-element array
    holding the f32-widened ε.  Queries must be padded to a tile
    multiple.
    """
    nq = u_f32.shape[0]
    n = table_hi.shape[0]
    kn = keys_hi.shape[0]
    rn = rank0_i32.shape[0]
    assert nq % tile_q == 0, "pad queries to a tile multiple (see ops.py)"
    grid = (nq // tile_q,)

    def qspec():
        return pl.BlockSpec((tile_q,), lambda i: (i,))

    def full(m):
        return pl.BlockSpec((m,), lambda i: (0,))

    kernel = functools.partial(_pgm_kernel, levels=levels, n=n, steps=steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            qspec(),  # u
            qspec(),  # q_hi
            qspec(),  # q_lo
            full(n),  # table_hi
            full(n),  # table_lo
            full(kn),  # keys_hi
            full(kn),  # keys_lo
            full(kn),  # pk_u0
            full(kn),  # pk_slope
            full(rn),  # rank0
            full(levels + 1),  # off
            full(levels + 1),  # off_r
            full(levels),  # sizes
            full(1),  # eps
        ],
        out_specs=qspec(),
        out_shape=jax.ShapeDtypeStruct((nq,), jnp.int32),
        interpret=interpret,
    )(
        u_f32,
        q_hi,
        q_lo,
        table_hi,
        table_lo,
        keys_hi,
        keys_lo,
        pk_u0,
        pk_slope,
        rank0_i32,
        off_i32,
        off_r_i32,
        sizes_i32,
        eps_i32,
    )


def _pgm_kernel_batched(
    u_ref,
    qhi_ref,
    qlo_ref,
    thi_ref,
    tlo_ref,
    khi_ref,
    klo_ref,
    u0_ref,
    slope_ref,
    r0_ref,
    off_ref,
    off_r_ref,
    sizes_ref,
    eps_ref,
    out_ref,
    *,
    levels: int,
    n: int,
    steps: int,
):
    # every block carries a leading table axis of extent 1: squeeze it
    # and reuse the single-table body verbatim (the rmi_search pattern)
    out_ref[0, :] = _pgm_body(
        u_ref[0],
        qhi_ref[0],
        qlo_ref[0],
        thi_ref[0],
        tlo_ref[0],
        khi_ref[0],
        klo_ref[0],
        u0_ref[0],
        slope_ref[0],
        r0_ref[0],
        off_ref[0],
        off_r_ref[0],
        sizes_ref[0],
        eps_ref[0, 0],
        levels=levels,
        n=n,
        steps=steps,
    )


def batched_pgm_search_pallas(
    u_f32,
    q_hi,
    q_lo,
    table_hi,
    table_lo,
    keys_hi,
    keys_lo,
    pk_u0,
    pk_slope,
    rank0_i32,
    off_i32,
    off_r_i32,
    sizes_i32,
    eps_i32,
    *,
    levels: int,
    steps: int,
    tile_q: int = DEFAULT_TILE_Q,
    interpret: bool = True,
):
    """Batched/tier variant of the fused PGM descent: ``(n_tables, nq)``
    queries against ``(n_tables, n)`` tables with per-table segment
    leaves and level directories.

    Grid is ``(table, q_tile)``; the index maps hand each program its
    table's parameter blocks (leading axis extent 1) and one query
    tile, so ONE ``pallas_call`` answers a whole batch/tier — the
    kernel-level analogue of the vmapped shared lookup.  The level
    count is static and common across tables (``_lift_pgm_levels``
    harmonised it at stack time); ``steps`` and ``eps_i32`` must cover
    the widest per-table window (extra Khuong–Morin trips are no-ops,
    which is why the stacked Index takes the max across tables).
    """
    nt, nq = u_f32.shape
    n = table_hi.shape[1]
    kn = keys_hi.shape[1]
    rn = rank0_i32.shape[1]
    assert nq % tile_q == 0, "pad queries to a tile multiple (see ops.py)"
    grid = (nt, nq // tile_q)

    def qspec():
        return pl.BlockSpec((1, tile_q), lambda t, i: (t, i))

    def per_table(m):
        return pl.BlockSpec((1, m), lambda t, i: (t, 0))

    kernel = functools.partial(_pgm_kernel_batched, levels=levels, n=n, steps=steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            qspec(),  # u
            qspec(),  # q_hi
            qspec(),  # q_lo
            per_table(n),  # table_hi
            per_table(n),  # table_lo
            per_table(kn),  # keys_hi
            per_table(kn),  # keys_lo
            per_table(kn),  # pk_u0
            per_table(kn),  # pk_slope
            per_table(rn),  # rank0
            per_table(levels + 1),  # off
            per_table(levels + 1),  # off_r
            per_table(levels),  # sizes
            per_table(1),  # eps
        ],
        out_specs=qspec(),
        out_shape=jax.ShapeDtypeStruct((nt, nq), jnp.int32),
        interpret=interpret,
    )(
        u_f32,
        q_hi,
        q_lo,
        table_hi,
        table_lo,
        keys_hi,
        keys_lo,
        pk_u0,
        pk_slope,
        rank0_i32,
        off_i32,
        off_r_i32,
        sizes_i32,
        eps_i32,
    )
