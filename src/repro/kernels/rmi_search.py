"""Fused RMI predict + ε-bounded branch-free search — Pallas TPU kernel.

TPU-native adaptation of the paper's hottest path (DESIGN.md §3):

* 64-bit keys are carried as **two u32 limbs** (TPU vector units have no
  64-bit integer compare; the lexicographic limb compare is one select).
* The CDF coordinate ``u`` is pre-normalised **once** outside the kernel
  (f64 -> f32); all in-kernel arithmetic is f32/i32.  The build widens
  each leaf's ε by the measured f32 rounding error so the window stays a
  guarantee.
* Grid over query tiles; the table limbs + leaf parameter arrays live in
  VMEM (VMEM-tier tables — the paper's L1/L2 regime; HBM-tier tables use
  the XLA path in :mod:`repro.core`).
* The bounded search is the fixed-trip Khuong–Morin loop: ``steps``
  iterations of gather + select, no data-dependent control flow.

Two entry points share one kernel body: :func:`fused_rmi_search_pallas`
(single table, grid over query tiles) and
:func:`batched_rmi_search_pallas` (a tier/batch of same-shape tables,
grid over ``(table, q_tile)`` with per-table parameter blocks) — the
latter is what lets :class:`repro.tune.batched.BatchedIndexes` and the
sharded tier dispatch ``backend="pallas"``.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

DEFAULT_TILE_Q = 512

# Kernel-body constants carry explicit 32-bit dtypes: under the global
# x64 flag a bare Python scalar traces as a 64-bit constant plus a
# convert, which Mosaic cannot lower.  NumPy scalars trace as typed
# literals (a jnp scalar would be a captured constant, also refused).
_ZERO = np.int32(0)
_ONE = np.int32(1)
_F32_LO = np.float32(-1.0e9)
_F32_HI = np.float32(1.0e9)


def _le_u64(khi, klo, qhi, qlo):
    """(khi,klo) <= (qhi,qlo) as unsigned 64-bit via u32 limbs."""
    return (khi < qhi) | ((khi == qhi) & (klo <= qlo))


def _rmi_body(u, qhi, qlo, thi, tlo, c, slope_a, icept_a, eps_a, rlo_a, rhi_a, *, b, n, steps):
    """The fused predict + bounded-search math on plain arrays.

    Shared by the single-table and batched kernels; every operand is a
    value (not a Ref), so the batched kernel can feed it per-table
    blocks squeezed down to the same shapes.
    """
    # --- stage 1: root -> leaf ---
    # clamp BEFORE the i32 cast: model blow-ups on key gaps predict
    # |p| ~ 1e15 in f32, and an out-of-range float->int32 cast is
    # implementation-defined garbage that survives the later clips.
    p_root = ((c[3] * u + c[2]) * u + c[1]) * u + c[0]
    p_root = jnp.clip(p_root, _F32_LO, _F32_HI)  # b/n <= 1 keeps the product in i32
    scale = np.float32(b / n)
    leaf = jnp.floor(p_root * scale).astype(jnp.int32)
    leaf = jnp.clip(leaf, _ZERO, np.int32(b - 1))

    # --- stage 2: leaf linear predict + guaranteed window ---
    slope = jnp.take(slope_a, leaf)
    icept = jnp.take(icept_a, leaf)
    eps = jnp.take(eps_a, leaf)
    rlo = jnp.take(rlo_a, leaf)
    rhi = jnp.take(rhi_a, leaf)
    p = jnp.clip(slope * u + icept, _F32_LO, _F32_HI)  # +/-eps stays inside i32
    # clamp the predicted CENTER into the leaf fences before widening: a
    # prediction blown far past the leaf (f32 u collapse on dense
    # clusters) would otherwise collapse the ±ε window to one fence
    # slot; the true rank is always inside [rlo, rhi], so clamping the
    # center never increases |center - true|.
    p_lo = jnp.clip(jnp.floor(p).astype(jnp.int32), rlo, rhi)
    p_hi = jnp.clip(jnp.ceil(p).astype(jnp.int32), rlo, rhi)
    lo = jnp.clip(p_lo - eps, rlo, rhi)
    hi = jnp.clip(p_hi + eps, rlo, rhi)

    # --- stage 3: fixed-trip branch-free bounded search ---
    base = lo
    length = hi - lo + 1

    def body(_, carry):
        base, length = carry
        half = length >> _ONE
        mid = base + half
        khi = jnp.take(thi, mid)
        klo = jnp.take(tlo, mid)
        go_right = _le_u64(khi, klo, qhi, qlo) & (length > _ONE)
        base = jnp.where(go_right, mid, base)
        length = length - jnp.where(length > _ONE, half, _ZERO)
        return base, length

    base, _ = lax.fori_loop(0, steps, body, (base, length))
    le = _le_u64(jnp.take(thi, base), jnp.take(tlo, base), qhi, qlo)
    return base + le.astype(jnp.int32) - _ONE


def _rmi_kernel(
    u_ref,
    qhi_ref,
    qlo_ref,
    thi_ref,
    tlo_ref,
    root_ref,
    slope_ref,
    icept_ref,
    eps_ref,
    rlo_ref,
    rhi_ref,
    out_ref,
    *,
    b: int,
    n: int,
    steps: int,
):
    out_ref[...] = _rmi_body(
        u_ref[...],  # (TQ,) f32, pre-normalised and clamped to [0,1]
        qhi_ref[...],
        qlo_ref[...],
        thi_ref[...],  # (N,) u32 table limbs
        tlo_ref[...],
        root_ref[...],  # (4,) f32
        slope_ref[...],
        icept_ref[...],
        eps_ref[...],
        rlo_ref[...],
        rhi_ref[...],
        b=b,
        n=n,
        steps=steps,
    )


def fused_rmi_search_pallas(
    u_f32,
    q_hi,
    q_lo,
    table_hi,
    table_lo,
    root_coef,
    leaf_slope,
    leaf_icept,
    leaf_eps,
    leaf_rlo,
    leaf_rhi,
    *,
    steps: int,
    tile_q: int = DEFAULT_TILE_Q,
    interpret: bool = True,
):
    """pallas_call wrapper.  Queries must be padded to a tile multiple."""
    nq = u_f32.shape[0]
    n = table_hi.shape[0]
    b = leaf_slope.shape[0]
    assert nq % tile_q == 0, "pad queries to a tile multiple (see ops.py)"
    grid = (nq // tile_q,)

    def qspec():
        return pl.BlockSpec((tile_q,), lambda i: (i,))

    def full(shape):
        return pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape))

    kernel = functools.partial(_rmi_kernel, b=b, n=n, steps=steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            qspec(),  # u
            qspec(),  # q_hi
            qspec(),  # q_lo
            full((n,)),  # table_hi
            full((n,)),  # table_lo
            full((4,)),  # root coef
            full((b,)),  # slope
            full((b,)),  # icept
            full((b,)),  # eps
            full((b,)),  # rlo
            full((b,)),  # rhi
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
        root_coef,
        leaf_slope,
        leaf_icept,
        leaf_eps,
        leaf_rlo,
        leaf_rhi,
    )


def _rmi_kernel_batched(
    u_ref,
    qhi_ref,
    qlo_ref,
    thi_ref,
    tlo_ref,
    root_ref,
    slope_ref,
    icept_ref,
    eps_ref,
    rlo_ref,
    rhi_ref,
    out_ref,
    *,
    b: int,
    n: int,
    steps: int,
):
    # every block carries a leading table axis of extent 1: squeeze it
    # and reuse the single-table body verbatim
    out_ref[0, :] = _rmi_body(
        u_ref[0],
        qhi_ref[0],
        qlo_ref[0],
        thi_ref[0],
        tlo_ref[0],
        root_ref[0],
        slope_ref[0],
        icept_ref[0],
        eps_ref[0],
        rlo_ref[0],
        rhi_ref[0],
        b=b,
        n=n,
        steps=steps,
    )


def batched_rmi_search_pallas(
    u_f32,
    q_hi,
    q_lo,
    table_hi,
    table_lo,
    root_coef,
    leaf_slope,
    leaf_icept,
    leaf_eps,
    leaf_rlo,
    leaf_rhi,
    *,
    steps: int,
    tile_q: int = DEFAULT_TILE_Q,
    interpret: bool = True,
):
    """Batched/tier variant: ``(n_tables, nq)`` queries against
    ``(n_tables, n)`` tables with per-table leaf parameters.

    Grid is ``(table, q_tile)``; the index maps hand each program its
    table's parameter blocks (leading axis extent 1) and one query tile,
    so one trace answers the whole tier — the kernel-level analogue of
    the vmapped shared lookup.  ``steps`` must cover the *widest*
    per-table window (extra Khuong–Morin trips are no-ops, which is why
    the stacked Index takes the max across tables).
    """
    nt, nq = u_f32.shape
    n = table_hi.shape[1]
    b = leaf_slope.shape[1]
    assert nq % tile_q == 0, "pad queries to a tile multiple (see ops.py)"
    grid = (nt, nq // tile_q)

    def qspec():
        return pl.BlockSpec((1, tile_q), lambda t, i: (t, i))

    def per_table(m):
        return pl.BlockSpec((1, m), lambda t, i: (t, 0))

    kernel = functools.partial(_rmi_kernel_batched, b=b, n=n, steps=steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            qspec(),  # u
            qspec(),  # q_hi
            qspec(),  # q_lo
            per_table(n),  # table_hi
            per_table(n),  # table_lo
            per_table(4),  # root coef
            per_table(b),  # slope
            per_table(b),  # icept
            per_table(b),  # eps
            per_table(b),  # rlo
            per_table(b),  # rhi
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
        root_coef,
        leaf_slope,
        leaf_icept,
        leaf_eps,
        leaf_rlo,
        leaf_rhi,
    )
