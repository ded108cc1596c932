"""Lane-wide k-ary search — the TPU-native K-BFS (DESIGN.md §3).

The paper's K-BFS uses k≈3 because a CPU core pays one cache line per
fence probe.  On a TPU the VPU compares a query against **k = 128 fences
in one vector op**, so the optimal k is the lane width: each step costs
one (TILE_Q, K) gather + compare + popcount-style reduce and shrinks the
window by 128x.  ceil(log_128 n) steps + one final lane sweep replace
ceil(log_2 n) dependent gathers — an 18->4 step reduction for n = 1M.

Keys are u32-limb pairs as in :mod:`rmi_search`.
"""

from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .rmi_search import _le_u64, _ONE, DEFAULT_TILE_Q

LANES = 128


def kary_owner_route(boundaries, q, *, k: int = LANES):
    """Branch-free owner-shard selection on a fence array.

    ``boundaries`` holds the first key of shards ``1..S-1`` (sorted); the
    owner of query ``q`` is ``#{i : boundaries[i] <= q}`` in ``[0, S-1]``
    — exact fence keys route to the shard that starts with them.  Up to
    ``k`` fences (every realistic tier) this is ONE lane-wide compare +
    popcount-style reduce, the same shape as a single :func:`_kary_kernel`
    step; beyond that it falls back to k-ary splitting.
    """
    nb = int(boundaries.shape[0])
    if nb == 0:
        return jnp.zeros(q.shape, dtype=jnp.int32)
    if nb <= k:
        le = boundaries[None, :] <= q[:, None]
        return jnp.sum(le.astype(jnp.int32), axis=-1)
    from repro.core import search

    lo = jnp.zeros(q.shape, dtype=jnp.int64)
    ln = jnp.full(q.shape, nb, dtype=jnp.int64)
    steps = max(1, int(math.ceil(math.log(nb) / math.log(k))))
    ub = search.bounded_kary_upper_bound(boundaries, q, lo, ln, k=k, steps=steps)
    return ub.astype(jnp.int32)


def _kary_body(qhi, qlo, thi, tlo, *, n: int, k: int, steps: int):
    """The lane-wide k-ary search on plain arrays (shared by the
    single-table and batched kernels)."""
    tq = qhi.shape[0]
    k32 = np.int32(k)  # 32-bit constants: see rmi_search._ZERO

    base = jnp.zeros((tq,), jnp.int32)
    length = jnp.full((tq,), n, jnp.int32)
    frac = lax.broadcasted_iota(jnp.int32, (tq, k - 1), 1) + _ONE  # 1..k-1

    def body(_, carry):
        base, length = carry
        fence = base[:, None] + (frac * length[:, None]) // k32  # (TQ, K-1)
        fhi = jnp.take(thi, fence)
        flo = jnp.take(tlo, fence)
        le = _le_u64(fhi, flo, qhi[:, None], qlo[:, None])
        seg = jnp.sum(le, axis=1, dtype=jnp.int32)  # segment index
        new_base = base + (seg * length) // k32
        new_len = (jnp.minimum(seg + _ONE, k32) * length) // k32 - (seg * length) // k32
        keep = length > k32
        base = jnp.where(keep, new_base, base)
        length = jnp.where(keep, new_len, length)
        return base, length

    base, length = lax.fori_loop(0, steps, body, (base, length))

    # final lane sweep: window now <= k wide; one (TQ, K) gather + count
    offs = lax.broadcasted_iota(jnp.int32, (tq, k), 1)
    idx = jnp.minimum(base[:, None] + offs, np.int32(n - 1))
    vhi = jnp.take(thi, idx)
    vlo = jnp.take(tlo, idx)
    le = _le_u64(vhi, vlo, qhi[:, None], qlo[:, None]) & (offs < length[:, None])
    cnt = jnp.sum(le, axis=1, dtype=jnp.int32)
    return base + cnt - _ONE


def _kary_kernel(qhi_ref, qlo_ref, thi_ref, tlo_ref, out_ref, *, n: int, k: int, steps: int):
    out_ref[...] = _kary_body(
        qhi_ref[...], qlo_ref[...], thi_ref[...], tlo_ref[...], n=n, k=k, steps=steps
    )


def _kary_steps(n: int, k: int) -> int:
    """Splitting steps until the window is <= k (then one lane sweep)."""
    steps = max(0, int(math.ceil(math.log(max(n, 2)) / math.log(k))) - 1) + (
        1 if n > k else 0
    )
    # conservative: ensure k^steps * k >= n
    while k ** (steps + 1) < n:
        steps += 1
    return steps


def kary_search_pallas(
    q_hi,
    q_lo,
    table_hi,
    table_lo,
    *,
    k: int = LANES,
    tile_q: int = DEFAULT_TILE_Q,
    interpret: bool = True,
):
    nq = q_hi.shape[0]
    n = table_hi.shape[0]
    assert nq % tile_q == 0
    steps = _kary_steps(n, k)
    grid = (nq // tile_q,)

    kernel = functools.partial(_kary_kernel, n=n, k=k, steps=steps)
    qspec = pl.BlockSpec((tile_q,), lambda i: (i,))
    full = pl.BlockSpec((n,), lambda i: (0,))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qspec, qspec, full, full],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((nq,), jnp.int32),
        interpret=interpret,
    )(q_hi, q_lo, table_hi, table_lo)


def _kary_kernel_batched(qhi_ref, qlo_ref, thi_ref, tlo_ref, out_ref, *, n, k, steps):
    out_ref[0, :] = _kary_body(
        qhi_ref[0], qlo_ref[0], thi_ref[0], tlo_ref[0], n=n, k=k, steps=steps
    )


def batched_kary_search_pallas(
    q_hi,
    q_lo,
    table_hi,
    table_lo,
    *,
    k: int = LANES,
    tile_q: int = DEFAULT_TILE_Q,
    interpret: bool = True,
):
    """Batched/tier variant: ``(n_tables, nq)`` queries against
    ``(n_tables, n)`` tables, grid over ``(table, q_tile)``.

    The model-free Pallas baseline for the batched/sharded lookup of
    kinds without a fused kernel (same role :func:`kary_search_pallas`
    plays for single-table ``backend="pallas"``).
    """
    nt, nq = q_hi.shape
    n = table_hi.shape[1]
    assert nq % tile_q == 0
    steps = _kary_steps(n, k)
    grid = (nt, nq // tile_q)
    qspec = pl.BlockSpec((1, tile_q), lambda t, i: (t, i))
    per_table = pl.BlockSpec((1, n), lambda t, i: (t, 0))
    kernel = functools.partial(_kary_kernel_batched, n=n, k=k, steps=steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qspec, qspec, per_table, per_table],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((nt, nq), jnp.int32),
        interpret=interpret,
    )(q_hi, q_lo, table_hi, table_lo)
