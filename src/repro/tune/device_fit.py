"""Single-program device fit-to-serve: fit → assemble → install, one jit.

The classic shard refresh (``TunedTier.refresh``) round-trips through the
host: the fit produces numpy arrays, ``build``/``stack`` re-assemble the
Index leaves host-side, and only the final ``refresh_shard`` swap is a
donated device program.  This module closes that loop for the PGM and RS
kinds: :func:`device_refresh` compiles the WHOLE pipeline — pad the
merged keys to the tier's capacity row, run the O(log n)-depth
``fit="fast"`` corridor fit (or the exact chunked scan with
``fit="scan"``), assemble every stacked leaf (level recursion, flat
scatter concat, radix table, fused-kernel ``pk_*``/``rk_*`` re-encode)
with device segment ops, validate capacities/fences/trip-count budgets,
and install the new shard row into the *donated* tier — as ONE device
program with zero host syncs on the serve path.

Validity is a traced ``ok`` flag, not a host branch: every leaf installs
through ``where(ok, new, old)``, so a failed build (verified-ε miss,
capacity overflow, fence violation, trip-count budget) leaves the tier
bit-identical and serving never observes a torn state.  The caller reads
``ok`` lazily and falls back to the classic host refresh path — which is
exactly what :class:`repro.tune.rebuild.TunedTier` does when its policy
sets ``device_refresh=True`` (the ``device_refreshes`` obs counter
records ok/fallback outcomes).

Capacity-shape discipline: tier refreshes always fit on the padded
capacity-``m`` table (``shard_build_table``), so the leaf-level fit runs
with static ``n == m``; only the PGM *upper* levels carry traced live
counts, which the corridor drivers accept via their ``count`` argument.
A PGM that terminates in fewer levels than the tier refits degenerate
one-segment roots — bit-identical to ``_lift_pgm_levels`` — so the
recursion depth is the tier's static ``levels``, unconditionally.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.cdf import POS_DTYPE, bit_length_device, ceil_log2_device, segment_ids
from repro.core.limbs import LimbTable
from repro.core.pgm import FAST_CHUNK, pgm_device_slopes, pgm_fit_fast, pgm_segments_scan
from repro.core.radix_spline import rs_knots_fast, rs_knots_scan, rs_verified_eps
from repro.dist.sharded_index import ShardedIndex
from repro.index import Index, count_trace
from repro.kernels.ops import pgm_level_reencode_device, rs_kernel_arrays_device

_MAXKEY = jnp.uint64(np.iinfo(np.uint64).max)

#: Kinds whose shard refresh compiles as one donated device program.
DEVICE_REFRESH_KINDS = ("PGM", "RS")

#: Fit modes the device pipeline accepts (the exactness contract per
#: mode is documented in docs/build_pipeline.md).
DEVICE_FITS = ("fast", "scan")


def pad_sorted_table_device(row, count, m: int):
    """Device counterpart of ``sharded_index._pad_sorted_table``: extend
    the ``count``-key prefix of ``row`` to the full capacity ``m`` with
    the same strictly-increasing spread continuation of the last key
    (identical uint64 arithmetic, so the padded rows are bit-equal).

    Example::

        padded = pad_sorted_table_device(row, jnp.asarray(3), 8)
    """
    row = jnp.asarray(row, dtype=jnp.uint64)
    count = jnp.asarray(count, dtype=POS_DTYPE)
    last = jnp.take(row, count - 1)
    pad = (m - count).astype(jnp.uint64)
    room = _MAXKEY - last
    step = jnp.where(room >= pad, room // jnp.maximum(pad, jnp.uint64(1)), jnp.uint64(0))
    idx = jnp.arange(m, dtype=POS_DTYPE)
    k = jnp.maximum(idx - count + 1, 0).astype(jnp.uint64)
    return jnp.where(idx < count, row, last + k * step)


def _pgm_device_arrays(tier: Index, padded_tab, eps, fit: str, chunk: int):
    """Fit + assemble every stacked PGM leaf for one shard row, entirely
    on device.  Returns ``(arrays, ok)`` with ``arrays`` in the tier's
    exact leaf shapes/dtypes and ``ok`` the accumulated validity flag
    (fit verified-ε, root termination, capacity fits, trip-count
    budgets)."""
    m = padded_tab.shape[0]
    levels = tier.s("levels")
    K = int(tier.arrays["keys"].shape[1])
    R = int(tier.arrays["rank0"].shape[1])
    eps = jnp.asarray(eps, dtype=jnp.float64)
    ok = jnp.bool_(True)

    cur_u = padded_tab
    cur_f = padded_tab.astype(jnp.float64)
    cnt = jnp.asarray(m, dtype=POS_DTYPE)
    idx_m = jnp.arange(m, dtype=POS_DTYPE)
    lvls = []  # bottom-up: (keys_u, slopes, start, nseg, parent_cnt)
    for _ in range(levels):
        if fit == "fast":
            mask, fit_ok = pgm_fit_fast(cur_f, eps, chunk=chunk, count=cnt)
            ok &= fit_ok
        else:
            mask = pgm_segments_scan(cur_f, eps, count=cnt)
        slopes, start, _ = pgm_device_slopes(cur_f, mask, eps, count=cnt)
        nseg = jnp.sum(mask.astype(POS_DTYPE))
        sel = jnp.clip(start, 0, m - 1)
        nxt_u = jnp.where(idx_m < nseg, jnp.take(cur_u, sel), _MAXKEY)
        lvls.append((nxt_u, slopes, start, nseg, cnt))
        cur_u = nxt_u
        cur_f = nxt_u.astype(jnp.float64)
        cnt = nseg
    # the greedy must have terminated in a one-segment root within the
    # tier's level budget (a deeper model cannot stack — restack cue)
    ok &= cnt == 1
    lvls.reverse()  # root-first, the stacked flat-concat order

    sizes = jnp.stack([nseg for (_, _, _, nseg, _) in lvls])
    zero = jnp.zeros((1,), dtype=POS_DTYPE)
    off = jnp.concatenate([zero, jnp.cumsum(sizes)])
    off_r = jnp.concatenate([zero, jnp.cumsum(sizes + 1)])
    ok &= off[levels] <= K
    ok &= off_r[levels] <= R

    kmin = padded_tab[0].astype(jnp.float64)
    span = padded_tab[m - 1].astype(jnp.float64) - kmin
    inv_span = jnp.where(span > 0, 1.0 / jnp.where(span > 0, span, 1.0), 1.0)

    # flat scatter-concat at traced offsets; fills mirror the host
    # _pad_pow2 sentinels (max-key / zero slope / leaf-count rank0)
    keys_flat = jnp.full((K,), _MAXKEY, dtype=jnp.uint64)
    slope_flat = jnp.zeros((K,), dtype=jnp.float64)
    pk_u0_flat = jnp.full((K,), 1.0, dtype=jnp.float32)
    pk_slope_flat = jnp.zeros((K,), dtype=jnp.float32)
    rank0_flat = jnp.full((R,), m, dtype=POS_DTYPE)
    idx_m1 = jnp.arange(m + 1, dtype=POS_DTYPE)
    max_err = jnp.float64(0.0)
    for l, (lvl_keys, lvl_slopes, lvl_start, nseg, parent_cnt) in enumerate(lvls):
        child = lvls[l + 1][0] if l + 1 < levels else padded_tab
        child_cnt = lvls[l + 1][3] if l + 1 < levels else jnp.asarray(m, POS_DTYPE)
        u0_l, slope_u, err_l = pgm_level_reencode_device(
            lvl_keys, lvl_slopes, lvl_start, nseg, child, child_cnt, kmin, span, inv_span
        )
        max_err = jnp.maximum(max_err, err_l)
        tgt = jnp.where(idx_m < nseg, off[l] + idx_m, K)
        keys_flat = keys_flat.at[tgt].set(lvl_keys, mode="drop")
        slope_flat = slope_flat.at[tgt].set(lvl_slopes, mode="drop")
        pk_u0_flat = pk_u0_flat.at[tgt].set(u0_l, mode="drop")
        pk_slope_flat = pk_slope_flat.at[tgt].set(slope_u, mode="drop")
        # rank0: nseg starts then the parent-count sentinel
        vals_r = jnp.where(idx_m1 < nseg, jnp.pad(lvl_start, (0, 1)), parent_cnt)
        tgt_r = jnp.where(idx_m1 < nseg + 1, off_r[l] + idx_m1, R)
        rank0_flat = rank0_flat.at[tgt_r].set(vals_r, mode="drop")

    pk_eps = jnp.minimum(jnp.ceil(max_err) + 2.0, float(m)).astype(jnp.int32)
    # the fused descent's trip count must fit the tier's bucketed static
    pk_window = jnp.minimum(2 * (pk_eps.astype(POS_DTYPE) + 1) + 3, max(m, 2))
    ok &= ceil_log2_device(pk_window) <= tier.s("pksteps")
    # "epi" is eps-and-n derived, both static-identical to the tier row

    arrays = {
        "keys": keys_flat,
        "slope": slope_flat,
        "rank0": rank0_flat,
        "off": off,
        "off_r": off_r,
        "sizes": sizes,
        "eps": eps.astype(jnp.int64).reshape(()),
        "pk_u0": pk_u0_flat,
        "pk_slope": pk_slope_flat,
        "pk_eps": pk_eps.reshape(()),
        "pk_kmin": kmin.reshape(()),
        "pk_inv_span": inv_span.reshape(()),
    }
    return arrays, ok


def _rs_device_arrays(tier: Index, padded_tab, eps, fit: str, chunk: int):
    """Fit + assemble every stacked RadixSpline leaf for one shard row,
    entirely on device.  Returns ``(arrays, ok)``."""
    m = padded_tab.shape[0]
    r_bits = tier.s("r_bits")
    Kc = int(tier.arrays["knot_keys"].shape[1])
    eps = jnp.asarray(eps, dtype=jnp.float64)
    keys_f = padded_tab.astype(jnp.float64)

    if fit == "fast":
        kmask, ok = rs_knots_fast(keys_f, eps, chunk=chunk)
    else:
        kmask = rs_knots_scan(keys_f, eps)
        ok = jnp.bool_(True)
    _, kpos = segment_ids(kmask)
    m_valid = jnp.sum(kmask.astype(POS_DTYPE))
    ok &= m_valid <= Kc

    # knot rows at tier capacity (Kc <= m: the capacity table is a power
    # of two and a spline never has more knots than keys)
    ids = jnp.arange(Kc, dtype=POS_DTYPE)
    sel = jnp.clip(jnp.take(kpos, jnp.minimum(ids, m - 1)), 0, m - 1)
    live = ids < m_valid
    kk = jnp.where(live, jnp.take(padded_tab, sel), _MAXKEY)
    kr = jnp.where(live, sel, m - 1)

    kmin_u = padded_tab[0]
    span_u = padded_tab[m - 1] - kmin_u
    span_bits = jnp.maximum(bit_length_device(span_u), 1).astype(POS_DTYPE)
    # r_bits is a structural static: a shard whose key span shrank below
    # it cannot install (host build would lower r_bits -> restack cue)
    ok &= span_bits >= r_bits
    shift = jnp.maximum(span_bits - r_bits, 0).astype(jnp.uint64)

    # radix table: device searchsorted over the capacity knot row; the
    # max-key pads rank at/above 2^r_bits, and clipping to m_valid makes
    # every entry equal to the host's valid-knots-only searchsorted
    pref_cap = jnp.uint64((1 << r_bits) + 1)
    prefixes = jnp.minimum((kk - kmin_u) >> shift, pref_cap).astype(POS_DTYPE)
    rt = jnp.searchsorted(prefixes, jnp.arange((1 << r_bits) + 1, dtype=POS_DTYPE), side="left")
    rt = jnp.minimum(rt, m_valid).astype(POS_DTYPE)

    # post-build verified bound: same clipped-interpolation formula as
    # build_rs, so eps_eff is bit-identical given the same knots
    meas = rs_verified_eps(keys_f, kmask)
    eps_eff = jnp.maximum(jnp.ceil(meas).astype(POS_DTYPE) + 1, 1)

    kmin_f = kmin_u.astype(jnp.float64)
    span_f = padded_tab[m - 1].astype(jnp.float64) - kmin_f
    inv_span = jnp.where(span_f > 0, 1.0 / jnp.where(span_f > 0, span_f, 1.0), 1.0)
    rk_u0, rk_slope, rk_eps = rs_kernel_arrays_device(
        kk, kr, m_valid, padded_tab, kmin_f, span_f, inv_span
    )

    # trip-count budgets against the tier's bucketed statics
    ok &= ceil_log2_device(m_valid) <= tier.s("ksteps")
    ok &= ceil_log2_device(jnp.minimum(2 * eps_eff + 3, max(m, 2))) <= tier.s("epi")
    rk_window = jnp.minimum(2 * rk_eps.astype(POS_DTYPE) + 3, max(m, 2))
    ok &= ceil_log2_device(rk_window) <= tier.s("rk_epi")

    arrays = {
        "knot_keys": kk,
        "knot_ranks": kr,
        "radix_table": rt,
        "kmin": kmin_u.reshape(()),
        "shift": shift.reshape(()),
        "eps_eff": eps_eff.reshape(()),
        "m_valid": m_valid.reshape(()),
        "rk_u0": rk_u0,
        "rk_slope": rk_slope,
        "rk_eps": rk_eps.reshape(()),
        "rk_kmin": kmin_f.reshape(()),
        "rk_inv_span": inv_span.reshape(()),
    }
    return arrays, ok


_KIND_DEVICE_ARRAYS = {"PGM": _pgm_device_arrays, "RS": _rs_device_arrays}


@partial(
    jax.jit, static_argnames=("shard", "fit", "chunk", "assemble"), donate_argnums=(0,)
)
def _device_refresh_impl(
    sidx: ShardedIndex, row, count, eps, shard: int, fit: str, chunk: int, assemble
):
    """The single donated device program: pad → fit → assemble →
    validate → ok-gated install.  Returns ``(new_sidx, ok)``; on
    ``ok == False`` every leaf keeps its old value, so the returned tier
    serves bit-identically to the input.  ``assemble`` is the kind's
    device-arrays builder, resolved host-side and passed static."""
    kind = sidx.index.kind
    count_trace(f"refresh:{kind}", f"device:{fit}")
    m = int(sidx.tables.shape[1])
    n_shards = sidx.n_shards  # static: derived from the stacked leaf shape
    padded_tab = pad_sorted_table_device(row, count, m)
    new_arrays, ok = assemble(sidx.index, padded_tab, eps, fit, chunk)

    # fence discipline, on device (same checks refresh_shard raises for)
    if shard > 0:
        prev_last = sidx.tables[shard - 1].take(sidx.counts[shard - 1] - 1).combine()
        ok &= jnp.take(row, 0) > prev_last
    if shard + 1 < n_shards:
        ok &= jnp.take(row, count - 1) < sidx.fences[shard + 1]

    def install(new, old):
        return jnp.where(ok, new.astype(old.dtype), old)

    arrays = {
        k: v.at[shard].set(install(new_arrays[k], v[shard]))
        for k, v in sidx.index.arrays.items()
    }
    counts = sidx.counts.at[shard].set(install(count, sidx.counts[shard]))
    offsets = jnp.concatenate([jnp.zeros((1,), POS_DTYPE), jnp.cumsum(counts)[:-1]])
    out = ShardedIndex(
        index=Index(kind, sidx.index.static, arrays),
        tables=sidx.tables.set_row(
            shard, jax.tree.map(install, LimbTable.split(padded_tab), sidx.tables[shard])
        ),
        fences=sidx.fences.at[shard].set(install(jnp.take(row, 0), sidx.fences[shard])),
        counts=counts,
        offsets=offsets,
    )
    return out, ok


def device_refresh(
    sidx: ShardedIndex,
    shard: int,
    merged,
    eps,
    *,
    fit: str = "fast",
    chunk: int = FAST_CHUNK,
):
    """Rebuild + hot-swap one shard as a single donated device program.

    ``merged`` is the shard's raw (unpadded, sorted, unique) key set and
    ``eps`` the tier spec's ε; the fit, every leaf assembly, the fence
    and trip-count validation, and the install all run inside ONE jit
    with the old tier donated — zero host transfers besides the merged
    key row itself.  ``fit="fast"`` uses the O(log n)-depth corridor fit
    (verified-ε checked on device); ``fit="scan"`` uses the exact
    chunked scan and produces bit-identical models to the host build.

    Returns ``(new_sidx, ok)`` where ``ok`` is a *device* bool the
    caller may read lazily: when False the returned tier is
    bit-identical to the input and the caller should fall back to the
    classic host refresh (:class:`repro.tune.rebuild.TunedTier` with
    ``RebuildPolicy(device_refresh=True)`` does, counting outcomes in
    the ``device_refreshes`` obs metric).

    Raises ``ValueError`` host-side only for conditions that require a
    restack anyway (kind unsupported, shard over capacity) — the same
    cues ``refresh_shard`` raises for.

    Example::

        sidx, ok = device_refresh(sidx, 1, merged_keys, eps=64)
        if not bool(ok):  # lazy host sync, off the serve path
            ...  # classic host refresh
    """
    kind = sidx.index.kind
    if kind not in DEVICE_REFRESH_KINDS:
        raise ValueError(
            f"device_refresh supports kinds {DEVICE_REFRESH_KINDS}, not {kind!r}"
        )
    if fit not in DEVICE_FITS:
        raise ValueError(f"unknown device fit {fit!r}; choose from {DEVICE_FITS}")
    merged = np.asarray(merged, dtype=np.uint64)
    m = int(sidx.tables.shape[1])
    if not 0 < len(merged) <= m:
        raise ValueError(
            f"shard has {len(merged)} keys for table capacity {m}: restack the tier"
        )
    if m < 2:
        raise ValueError("capacity-1 tier: use the host refresh path")
    row = np.zeros(m, dtype=np.uint64)
    row[: len(merged)] = merged
    return _device_refresh_impl(
        sidx,
        jnp.asarray(row),
        jnp.asarray(len(merged), dtype=POS_DTYPE),
        jnp.asarray(float(eps), dtype=jnp.float64),
        shard,
        fit,
        int(chunk),
        _KIND_DEVICE_ARRAYS[kind],
    )
