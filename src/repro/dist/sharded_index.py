"""Sharded multi-table predecessor lookup under ``shard_map``.

A serving tier holds *many* sorted tables — one per shard of a
partitioned keyspace — and an :class:`~repro.index.Index` is a pytree
precisely so a tier of same-spec per-shard indexes can be **stacked
leaf-wise** into one :class:`ShardedIndex` whose leading axis is the
shard axis.  One ``shard_map`` over the ``tp`` logical axis of
:class:`~repro.dist.sharding.ShardingCtx` then queries the whole tier
with a four-stage pipeline:

1. **fence** — every device holds the (tiny, replicated) fence array of
   shard boundary keys; a branch-free lane-wide k-ary compare
   (:func:`repro.kernels.kary_search.kary_owner_route`) assigns each
   resident query its owner shard.  Exact fence keys route to the shard
   that *starts* with them.
2. **route** — queries are bucketed by owner (argsort + branch-free
   boundary search, the ``_a2a_lookup`` pattern from
   :mod:`repro.models.embedding`) into a capacity-factored
   ``(n_shards, cap)`` request matrix and exchanged with ONE
   ``lax.all_to_all``.
3. **answer** — each shard answers the requests it owns against its
   *resident* index leaf through the shared traceable query body
   (:func:`repro.index.lookup_impl` — same code path as single-table
   ``Index.lookup``, so results are bit-identical to the concatenated
   reference), then maps local ranks to global ranks via its offset.
4. **return** — a second ``all_to_all`` carries global ranks back to the
   requesting device, where they are scattered into query order.

**Capacity-factor overflow policy**: the request matrix gives each
(source, owner) pair ``cap = ceil(cap_factor * B_local / n_shards)``
slots.  Queries beyond capacity (pathologically skewed batches) are NOT
silently mis-answered: they are dropped at the route stage and come back
as :data:`DROPPED` (``-2``), distinguishable from the legitimate
"before the first key" rank ``-1``.  Raise ``cap_factor`` for an
exactness guarantee (``cap_factor >= n_shards`` can never drop).

Two fallback modes complete the picture:

* ``mode="allgather"`` — for small tiers: queries stay replicated, every
  shard answers its owned subset and one ``psum`` merges the masked
  results (collective = the (B,) rank vector, no routing latency).
* single-device / mismatched mesh — a vmapped all-shards sweep with an
  owner-select, bit-identical semantics with zero collectives.

Heterogeneous shard sizes share one trace: local tables are padded to a
common power-of-two length with a strictly increasing continuation of
the last key (a clamp against the per-shard valid count restores exact
ranks), and variable-length index leaves reuse the power-of-two sentinel
padding idiom of :mod:`repro.index.impls`.

The stacked tables stay resident as u32 limb planes
(:class:`~repro.core.limbs.LimbTable`), split on the host when a row is
written: a u64 table operand would be split whole into limbs at the
entry of every lookup program, on a chip with no 64-bit integer unit.

Rebuilds swap in without host round-trips: :func:`refresh_shard` donates
the old stacked pytree to a jitted ``.at[shard].set`` update
(``donate_argnums=0``), recomputing offsets on device.
"""

from __future__ import annotations

import json
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.cdf import POS_DTYPE
from repro.core.limbs import LimbTable
from repro.core.search import NO_PRED
from repro.index import (
    Index,
    batched_pallas_impl,
    count_trace,
    count_u64_table,
    lookup_impl,
    registry,
)
from repro.index.specs import IndexSpec

from . import collectives

#: Rank reported for queries dropped by the capacity-factored exchange.
#: Distinct from :data:`repro.core.search.NO_PRED` (re-exported above),
#: the shared below-the-global-min sentinel.
DROPPED = -2

# ---------------------------------------------------------------------------
# Tier telemetry: routing imbalance + drop-rate counters.
#
# The counters live in the repro.obs registry (``route_*`` metrics,
# labeled by tier — "all" is the process-wide aggregate); everything
# below is a thin view so the PR 2 call signatures keep working.  obs is
# imported lazily inside the telemetry functions only: the telemetry-off
# lookup path never pulls repro.obs in at call time.
# ---------------------------------------------------------------------------

#: the tier label the global aggregate view reads
_ALL_TIERS = "all"


def _fresh_tier_metrics() -> dict:
    """A zeroed caller-owned ``telemetry_sink`` dict (the PR 2 shape)."""
    return {
        "lookups": 0,
        "queries": 0,
        "dropped": 0,
        "routed_max": 0,  # busiest shard's queries, summed over lookups
        "routed_even": 0.0,  # perfectly even per-shard load, summed
        "imbalance_last": 0.0,
        "imbalance_peak": 0.0,
    }


def reset_tier_metrics() -> None:
    """Zero the registry-backed ``route_*`` counters (every tier label,
    including the per-:class:`~repro.tune.rebuild.TunedTier` ones).

    Caller-owned ``telemetry_sink`` dicts are **not** reset — the sink
    contract is that the caller owns that dict's lifetime; zero it
    yourself (or take a fresh :func:`_fresh_tier_metrics`)."""
    from repro import obs

    obs.reset(prefix="route_")


def derived_tier_metrics(counters: dict) -> dict:
    """Raw routing counters + the derived rates (drop rate, mean
    imbalance) — shared by the global view and per-tier sinks.  Missing
    keys count as zero, so a zero-query (or empty) snapshot yields
    well-defined 0.0 rates instead of dividing by zero."""
    m = {**_fresh_tier_metrics(), **counters}
    m["drop_rate"] = m["dropped"] / m["queries"] if m["queries"] else 0.0
    m["imbalance_mean"] = m["routed_max"] / m["routed_even"] if m["routed_even"] else 0.0
    return m


def _tier_counters_from_obs(tier: str) -> dict:
    """Render one tier label's ``route_*`` registry samples back into the
    PR 2 counter-dict shape."""
    from repro import obs

    snap = obs.snapshot(prefix="route_")
    v = lambda name: obs.sample_value(snap, name, tier=tier)
    return {
        "lookups": int(v("route_lookups")),
        "queries": int(v("route_queries")),
        "dropped": int(v("route_dropped")),
        "routed_max": int(v("route_max")),
        "routed_even": v("route_even"),
        "imbalance_last": v("route_imbalance_last"),
        "imbalance_peak": v("route_imbalance_peak"),
    }


def tier_metrics() -> dict:
    """Routing-imbalance and drop-rate counters across every telemetry-
    enabled :func:`sharded_lookup` in the process since the last reset.

    ``imbalance_*`` is the busiest shard's load over the perfectly even
    load (1.0 = uniform routing; ``n_shards`` = fully skewed);
    ``drop_rate`` is the fraction of queries returned as
    :data:`DROPPED` by the capacity-factored exchange.  Surfaced by
    ``DecodeEngine.metrics()`` next to the lookup trace counts.  A
    caller serving several tiers passes its own ``telemetry_sink`` (or a
    ``telemetry_label``, which adds a per-tier ``route_*`` labelset in
    the registry) to :func:`sharded_lookup` for per-tier attribution;
    the global view here aggregates all of them.  Rendered from the
    ``repro.obs`` registry — ``obs.snapshot(prefix="route_")`` exposes
    the same counters with labels.
    """
    return derived_tier_metrics(_tier_counters_from_obs(_ALL_TIERS))


@partial(jax.jit, static_argnames=("n_shards",))
def _owner_histogram(fences, queries, n_shards: int):
    count_trace("obs:owner_hist", "jit")
    owners = route_owners(fences, queries)
    return jnp.bincount(owners.astype(jnp.int32), length=n_shards)


def _record_tier_metrics(
    sidx: "ShardedIndex",
    queries,
    out,
    sink: dict | None = None,
    label: str | None = None,
) -> None:
    """One telemetry-on call's routing counters.  In a profiler trace the
    host spans ``tier.telemetry.pull`` (the two device pulls, which wait
    for the lookup) and ``tier.telemetry.record`` (registry and sink
    writes) mark its two halves, inside ``tier.telemetry``."""
    from repro import obs

    with obs.span("tier.telemetry"):
        with obs.span("tier.telemetry.pull"):
            hist = np.asarray(_owner_histogram(sidx.fences, queries, sidx.n_shards))
            dropped = int(np.asarray(out == DROPPED).sum())
        with obs.span("tier.telemetry.record"):
            b = int(hist.sum())
            even = b / sidx.n_shards
            imb = float(hist.max() / even) if even > 0 else 0.0
            tiers = [_ALL_TIERS] if label is None else [_ALL_TIERS, str(label)]
            for t in tiers:
                obs.metric("route_lookups").inc(tier=t)
                obs.metric("route_queries").inc(b, tier=t)
                obs.metric("route_dropped").inc(dropped, tier=t)
                obs.metric("route_max").inc(int(hist.max()), tier=t)
                obs.metric("route_even").inc(even, tier=t)
                obs.metric("route_imbalance_last").set(imb, tier=t)
                obs.metric("route_imbalance_peak").max(imb, tier=t)
            if label is not None:
                # per-owner-shard histogram, labeled tiers only (the "all"
                # view would mix tiers of different shard counts): this is
                # the density estimate weighted_quantile_bounds rebalances from
                shard_q = obs.metric("route_shard_queries")
                for s, c in enumerate(hist):
                    if c:
                        shard_q.inc(int(c), tier=str(label), shard=s)
            if sink is not None:
                sink["lookups"] += 1
                sink["queries"] += b
                sink["dropped"] += dropped
                sink["routed_max"] += int(hist.max())
                sink["routed_even"] += even
                sink["imbalance_last"] = imb
                sink["imbalance_peak"] = max(sink["imbalance_peak"], imb)


def shard_query_weights(tier: str, n_shards: int) -> np.ndarray:
    """Observed per-owner-shard query counts for one labeled tier, read
    back from the ``route_shard_queries`` registry counter (zeros where a
    shard never owned a query).  The raw material of skew-aware
    rebalancing: :meth:`repro.tune.rebuild.TunedTier.maybe_rebalance`
    windows these counts to detect sustained drift."""
    from repro import obs

    snap = obs.snapshot(prefix="route_shard_queries")
    return np.asarray(
        [
            obs.sample_value(snap, "route_shard_queries", tier=str(tier), shard=s)
            for s in range(n_shards)
        ],
        dtype=np.float64,
    )


_MAXKEY = np.uint64(np.iinfo(np.uint64).max)

#: Static keys that hold bucketed loop trip counts: extra iterations are
#: no-ops, so stacking may take the max across shards.  ``pksteps`` /
#: ``rk_epi`` are the fused PGM / RadixSpline kernels' trip counts.
_STEP_KEYS = ("epi", "ksteps", "pksteps", "rk_epi")


def _pow2ceil(x: int) -> int:
    x = max(int(x), 1)
    return 1 << (x - 1).bit_length()


def _pad_to(arr: np.ndarray, shape: tuple) -> np.ndarray:
    """Pad ``arr`` up to ``shape`` with inert sentinels (the impls idiom):
    uint64 key arrays get the max-key sentinel, everything else repeats
    its last entry (edge replication)."""
    arr = np.asarray(arr)
    if arr.shape == tuple(shape):
        return arr
    widths = [(0, t - s) for s, t in zip(arr.shape, shape)]
    if any(w < 0 for _, w in widths):
        raise ValueError(f"cannot shrink leaf of shape {arr.shape} to {shape}")
    if arr.dtype == np.uint64:
        return np.pad(arr, widths, mode="constant", constant_values=_MAXKEY)
    return np.pad(arr, widths, mode="edge")


def _lift_pgm_levels(idx: Index, target: int) -> Index:
    """Lift a PGM-shaped index to ``target`` levels by prepending trivial
    one-segment root levels.

    ``build_pgm``'s recursion always terminates in a one-segment root, so
    a synthetic root (slope 0, ``rank0 = [0, 1]``) predicts window
    ``[0, 0]`` over the level below — the next-level search degenerates
    to the old root and the lifted index answers identically.  This is
    what makes PGM shard-stackable: per-shard level counts are
    data-dependent, and the shallow shards lift to the deepest one.
    """
    from repro.index.impls import _pad_pow2

    levels = idx.s("levels")
    extra = target - levels
    if extra == 0:
        return idx
    if extra < 0:
        raise ValueError(f"cannot lower a PGM from {levels} to {target} levels")
    sizes = np.asarray(idx.arrays["sizes"])
    keys = np.asarray(idx.arrays["keys"])
    slope = np.asarray(idx.arrays["slope"])
    rank0 = np.asarray(idx.arrays["rank0"])
    pk_u0 = np.asarray(idx.arrays["pk_u0"])
    pk_slope = np.asarray(idx.arrays["pk_slope"])
    kv = int(sizes.sum())  # valid prefix before the pow2 sentinel pad
    rv = int((sizes + 1).sum())
    new_keys = np.concatenate([np.full(extra, keys[0], keys.dtype), keys[:kv]])
    new_slope = np.concatenate([np.zeros(extra, slope.dtype), slope[:kv]])
    synth_rank0 = np.tile(np.asarray([0, 1], rank0.dtype), extra)
    new_rank0 = np.concatenate([synth_rank0, rank0[:rv]])
    # the synthetic roots anchor at keys[0], whose kernel coordinate is
    # pk_u0[0]; slope 0 keeps the fused descent's window at [0, 0] too
    new_pk_u0 = np.concatenate([np.full(extra, pk_u0[0], pk_u0.dtype), pk_u0[:kv]])
    new_pk_slope = np.concatenate([np.zeros(extra, pk_slope.dtype), pk_slope[:kv]])
    new_sizes = np.concatenate([np.ones(extra, sizes.dtype), sizes]).astype(np.int64)
    arrays = dict(idx.arrays)
    arrays["keys"] = jnp.asarray(_pad_pow2(new_keys, _MAXKEY))
    arrays["slope"] = jnp.asarray(_pad_pow2(new_slope, 0.0))
    arrays["rank0"] = jnp.asarray(_pad_pow2(new_rank0, new_rank0[-1]))
    arrays["pk_u0"] = jnp.asarray(_pad_pow2(new_pk_u0, np.float32(1.0)))
    arrays["pk_slope"] = jnp.asarray(_pad_pow2(new_pk_slope, np.float32(0.0)))
    arrays["sizes"] = jnp.asarray(new_sizes)
    arrays["off"] = jnp.asarray(np.concatenate([[0], np.cumsum(new_sizes)]).astype(np.int64))
    arrays["off_r"] = jnp.asarray(
        np.concatenate([[0], np.cumsum(new_sizes + 1)]).astype(np.int64),
    )
    static = tuple((k, target if k == "levels" else v) for k, v in idx.static)
    return Index(idx.kind, static, arrays, info=idx.info)


def _pad_gapped_leaves(idx: Index, target_l: int) -> Index:
    """Pad a GAPPED index to ``target_l`` leaves with *inert* rows:
    max-key ``keys``/``fences``/``route`` entries and **zero** counts.

    The generic :func:`_pad_to` edge-replicates integer leaves, which
    would fabricate live keys in the padded rows (``counts`` must be 0
    so the padded leaves hold nothing, absorb nothing at insert, and are
    skipped by compaction's valid mask); max-key route entries keep the
    model-guided owner search inside the real leaf range."""
    L, cap = (int(s) for s in idx.arrays["keys"].shape)
    if L == target_l:
        return idx
    if L > target_l:
        raise ValueError(f"cannot shrink a GAPPED index from {L} to {target_l} leaves")
    pad = target_l - L
    arrays = dict(idx.arrays)
    arrays["keys"] = jnp.concatenate(
        [idx.arrays["keys"], jnp.full((pad, cap), _MAXKEY, dtype=jnp.uint64)]
    )
    arrays["counts"] = jnp.concatenate(
        [idx.arrays["counts"], jnp.zeros((pad,), dtype=jnp.int64)]
    )
    arrays["fences"] = jnp.concatenate(
        [idx.arrays["fences"], jnp.full((pad,), _MAXKEY, dtype=jnp.uint64)]
    )
    arrays["route"] = jnp.concatenate(
        [idx.arrays["route"], jnp.full((pad,), _MAXKEY, dtype=jnp.uint64)]
    )
    return Index(idx.kind, idx.static, arrays, info=idx.info)


def _harmonize(kind: str, per_shard: list) -> list:
    """Make per-shard indexes structurally stackable where the kind
    allows it (PGM-shaped kinds: lift shallow shards to the max depth;
    GAPPED: pad shallow shards with inert zero-count leaves)."""
    if registry.entry(kind).query_key == "pgm":
        target = max(i.s("levels") for i in per_shard)
        return [_lift_pgm_levels(i, target) for i in per_shard]
    if kind == "GAPPED":
        target = max(int(i.arrays["keys"].shape[0]) for i in per_shard)
        return [_pad_gapped_leaves(i, target) for i in per_shard]
    return per_shard


def _merge_static(statics: list) -> tuple:
    """Merge per-shard static aux: bucketed trip counts take the max
    (extra bounded-search iterations are no-ops), everything structural
    (levels, fanout, degree, ...) must agree exactly."""
    merged = []
    for i, (name, v0) in enumerate(statics[0]):
        vals = [s[i][1] for s in statics]
        if any(s[i][0] != name for s in statics):
            raise ValueError("per-shard indexes have mismatched static keys")
        if name in _STEP_KEYS:
            merged.append((name, max(vals)))
        elif len(set(vals)) != 1:
            raise ValueError(
                f"cannot stack: static {name!r} differs across shards ({sorted(set(vals))}); "
                "structural statics must agree — rebuild with a shard-stable spec"
            )
        else:
            merged.append((name, v0))
    return tuple(merged)


def stack_indexes(indexes: list) -> Index:
    """Stack N same-spec per-shard indexes leaf-wise into one Index whose
    leaves carry a leading shard axis.  Leaf shapes are padded to the
    per-leaf max (power-of-two padding at build time makes collisions the
    common case), so heterogeneous shards share one stacked structure."""
    if not indexes:
        raise ValueError("need at least one index to stack")
    kinds = {i.kind for i in indexes}
    if len(kinds) != 1:
        raise ValueError(f"cannot stack indexes of different kinds: {sorted(kinds)}")
    names = set(indexes[0].arrays)
    if any(set(i.arrays) != names for i in indexes):
        raise ValueError("per-shard indexes have mismatched leaf names")
    static = _merge_static([i.static for i in indexes])
    arrays = {}
    for name in sorted(names):
        leaves = [np.asarray(i.arrays[name]) for i in indexes]
        if len({l_.ndim for l_ in leaves}) != 1:
            raise ValueError(f"leaf {name!r} rank differs across shards")
        target = tuple(max(dims) for dims in zip(*[l_.shape for l_ in leaves]))
        arrays[name] = jnp.stack([jnp.asarray(_pad_to(l_, target)) for l_ in leaves])
    info = {"n_shards": len(indexes), "name": f"sharded-{indexes[0].name}"}
    return Index(indexes[0].kind, static, arrays, info)


class ShardedIndex:
    """A tier of per-shard learned indexes over a partitioned keyspace.

    Attributes
    ----------
    index:   stacked :class:`Index` — every leaf has leading shard axis.
    tables:  ``(n_shards, m)`` :class:`~repro.core.limbs.LimbTable` —
             per-shard sorted uint64 tables as two u32 limb planes,
             padded to a common power-of-two ``m`` (strictly increasing
             pad); ``np.asarray(tables[s])`` reads row ``s`` as uint64.
    fences:  ``(n_shards,)`` uint64 — first key of each shard; the
             router searches ``fences[1:]``.
    counts:  ``(n_shards,)`` int64 — valid (unpadded) keys per shard.
    offsets: ``(n_shards,)`` int64 — global rank of each shard's first
             key (exclusive cumsum of ``counts``).
    """

    __slots__ = ("index", "tables", "fences", "counts", "offsets", "info")

    def __init__(self, index: Index, tables, fences, counts, offsets, info=None):
        object.__setattr__(self, "index", index)
        object.__setattr__(self, "tables", tables)
        object.__setattr__(self, "fences", fences)
        object.__setattr__(self, "counts", counts)
        object.__setattr__(self, "offsets", offsets)
        object.__setattr__(self, "info", dict(info or {}))

    # -- pytree protocol --------------------------------------------------
    def tree_flatten(self):
        children = (self.index, self.tables, self.fences, self.counts, self.offsets)
        return children, ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children, info=None)

    # -- metadata ---------------------------------------------------------
    @property
    def n_shards(self) -> int:
        return int(self.tables.shape[0])

    @property
    def kind(self) -> str:
        return self.index.kind

    def __repr__(self):
        return (
            f"ShardedIndex(kind={self.kind!r}, n_shards={self.n_shards}, "
            f"m={int(self.tables.shape[1])})"
        )

    def shard(self, s: int) -> Index:
        """The per-shard Index view of shard ``s`` (sliced leaves)."""
        return Index(
            self.index.kind,
            self.index.static,
            {k: v[s] for k, v in self.index.arrays.items()},
            info={"shard": s, **self.info},
        )

    def place(self, ctx) -> "ShardedIndex":
        """The tier laid out over ``ctx``'s ``tp`` axis: shard ``s``'s
        leaves, table row, count and offset on the devices of ``tp``
        index ``s``, the fences replicated — the layout the ``a2a`` and
        ``allgather`` modes read, so no call moves the tier."""
        from jax.sharding import NamedSharding

        axes = ctx.mesh_axes("tp")
        if ctx.n("tp") != self.n_shards:
            raise ValueError(
                f"mesh tp extent {ctx.n('tp')} does not match n_shards={self.n_shards}"
            )
        shard = NamedSharding(ctx.mesh, P(axes if len(axes) > 1 else axes[0]))
        put = partial(jax.device_put, device=shard)
        return ShardedIndex(
            index=jax.tree_util.tree_map(put, self.index),
            tables=put(self.tables),
            fences=jax.device_put(self.fences, NamedSharding(ctx.mesh, P())),
            counts=put(self.counts),
            offsets=put(self.offsets),
            info=self.info,
        )

    def space_bytes(self) -> int:
        """Model bytes across the tier + the router's fence/offset arrays."""
        per_shard = self.shard(0).space_bytes()
        router = self.fences.size * 8 + self.counts.size * 8 + self.offsets.size * 8
        return self.n_shards * per_shard + router

    # -- build ------------------------------------------------------------
    @staticmethod
    def build(kind_or_spec, table_np, n_shards: int, *, bounds=None, **params) -> "ShardedIndex":
        """Partition a global sorted table into ``n_shards`` contiguous
        shards, build one same-spec Index per shard, and stack.

        ``bounds`` (optional) overrides the even split with an explicit
        strictly increasing rank partition ``[0, ..., n]`` of length
        ``n_shards + 1`` — the skew-aware rebalancer's restack fallback
        (:func:`weighted_quantile_bounds` computes such partitions from
        observed traffic)."""
        table_np = np.asarray(table_np, dtype=np.uint64)
        n = len(table_np)
        if n_shards < 1 or n_shards > n:
            raise ValueError(f"n_shards={n_shards} must be in [1, {n}]")
        if isinstance(kind_or_spec, IndexSpec):
            spec = kind_or_spec
        else:
            spec = registry.spec_for(str(kind_or_spec), **params)
        if bounds is None:
            bounds = [round(i * n / n_shards) for i in range(n_shards + 1)]
        else:
            bounds = [int(b) for b in np.asarray(bounds).reshape(-1)]
            if (
                len(bounds) != n_shards + 1
                or bounds[0] != 0
                or bounds[-1] != n
                or any(b1 <= b0 for b0, b1 in zip(bounds, bounds[1:]))
            ):
                raise ValueError(
                    f"bounds must be a strictly increasing rank partition [0, ..., {n}] "
                    f"of length {n_shards + 1}, got {bounds}"
                )
        locals_ = [table_np[bounds[i] : bounds[i + 1]] for i in range(n_shards)]
        m = _pow2ceil(max(len(t) for t in locals_))
        padded = [_pad_sorted_table(t, m) for t in locals_]
        # self-contained kinds (GAPPED) own their keys: build them on the
        # raw shard tables so the pad continuation never becomes a live
        # key (an insert could otherwise land *above* a pad key and shift
        # intermediate ranks); ragged leaf counts harmonize at stacking
        from repro.index.impls import query_impl

        build_tables = locals_ if query_impl(spec.kind).lookup is not None else padded
        per_shard = [registry.entry(spec.kind).build(spec, p) for p in build_tables]
        stacked = stack_indexes(_harmonize(spec.kind, per_shard))
        counts = np.asarray([len(t) for t in locals_], dtype=np.int64)
        offsets = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int64)
        fences = np.asarray([t[0] for t in locals_], dtype=np.uint64)
        info = {"spec": spec.display_name(), "n": n, "m": m}
        return ShardedIndex(
            index=stacked,
            tables=LimbTable.from_u64(np.stack(padded)),
            fences=jnp.asarray(fences),
            counts=jnp.asarray(counts),
            offsets=jnp.asarray(offsets),
            info=info,
        )

    # -- serialization ----------------------------------------------------
    def save(self, path) -> None:
        """npz round-trip of the stacked tier: leaves stay bit-exact."""
        payload = {f"idx_{k}": np.asarray(v) for k, v in self.index.arrays.items()}
        payload.update(
            tables=np.asarray(self.tables),
            fences=np.asarray(self.fences),
            counts=np.asarray(self.counts),
            offsets=np.asarray(self.offsets),
        )
        meta = {
            "kind": self.index.kind,
            "static": list(map(list, self.index.static)),
            "info": {k: v for k, v in self.info.items() if isinstance(v, (str, int, float, bool))},
        }
        payload["__meta__"] = np.frombuffer(json.dumps(meta).encode(), dtype=np.uint8)
        np.savez(path, **payload)

    @classmethod
    def load(cls, path) -> "ShardedIndex":
        with np.load(path) as z:
            meta = json.loads(bytes(z["__meta__"]).decode())
            arrays = {k[len("idx_") :]: jnp.asarray(z[k]) for k in z.files if k.startswith("idx_")}
            tables = LimbTable.from_u64(z["tables"])
            fences = jnp.asarray(z["fences"])
            counts = jnp.asarray(z["counts"])
            offsets = jnp.asarray(z["offsets"])
        static = tuple((k, int(v)) for k, v in meta["static"])
        index = Index(meta["kind"], static, arrays, info=meta.get("info"))
        return cls(index, tables, fences, counts, offsets, info=meta.get("info"))


jax.tree_util.register_pytree_node_class(ShardedIndex)


def _pad_sorted_table(t: np.ndarray, m: int) -> np.ndarray:
    """Pad a local sorted table to length ``m`` with a strictly
    increasing continuation of its last key (``last+1, last+2, ...``).

    The table stays sorted *and unique*, so every per-kind builder's
    fitting code sees a well-formed table (duplicate padding makes
    least-squares segment fits degenerate), and the rank clamp against
    the shard's valid count maps any hit in the padded tail back to the
    true local predecessor (the last real key).  Padded keys may overlap
    the next shard's key range; that is harmless because the router
    never sends a query at or beyond the next fence to this shard.  In
    the degenerate no-headroom case (last key at the top of the u64
    range) the pad repeats the last key instead."""
    if len(t) == 0:
        raise ValueError("empty shard")
    pad = m - len(t)
    if pad < 0:
        raise ValueError(f"shard has {len(t)} keys > padded capacity {m}")
    if pad == 0:
        return t
    last = np.uint64(t[-1])
    room = int(_MAXKEY) - int(last)
    if room >= pad:
        # spread the pad across the remaining headroom: tightly clustered
        # pad keys make per-segment least-squares fits ill-conditioned
        step = np.uint64(room // pad)
        ext = last + np.arange(1, pad + 1, dtype=np.uint64) * step
    else:
        ext = np.full(pad, last, dtype=t.dtype)
    return np.concatenate([t, ext])


# ---------------------------------------------------------------------------
# Routing + local answer
# ---------------------------------------------------------------------------


def route_owners(fences, queries):
    """Owner shard per query: branch-free k-ary search on the fence
    array (``fences[0]`` is the global min and not a boundary)."""
    from repro.kernels.kary_search import kary_owner_route

    return kary_owner_route(fences[1:], queries)


def _answer_local(local_index: Index, local_table, count, offset, queries, backend: str):
    """Resident-shard answer: shared per-kind lookup on the local leaf,
    local rank clamped to the valid count and rebased to a global rank."""
    r = lookup_impl(local_index, local_table, queries, backend)
    r = jnp.minimum(r.astype(POS_DTYPE), count - 1)
    return jnp.where(r < 0, jnp.asarray(NO_PRED, POS_DTYPE), offset + r)


# ---------------------------------------------------------------------------
# Single-device / mismatched-mesh fallback: vmapped all-shards sweep
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("backend",))
def _lookup_vmapped(sidx: ShardedIndex, queries, backend: str):
    count_trace(f"sharded:{sidx.kind}", f"ref:{backend}")
    count_u64_table("tier", sidx.tables)
    owners = route_owners(sidx.fences, queries)

    if backend == "pallas":
        # one batched (table, q_tile)-grid kernel answers every shard;
        # clamp + rebase mirror _answer_local exactly
        bq = jnp.broadcast_to(queries[None, :], (sidx.n_shards, queries.shape[0]))
        r = batched_pallas_impl(sidx.index, sidx.tables, bq)
        r = jnp.minimum(r.astype(POS_DTYPE), sidx.counts[:, None] - 1)
        granks = jnp.where(r < 0, jnp.asarray(NO_PRED, POS_DTYPE), sidx.offsets[:, None] + r)
    else:

        def one(idx, tab, cnt, off):
            return _answer_local(idx, tab, cnt, off, queries, backend)

        granks = jax.vmap(one)(sidx.index, sidx.tables, sidx.counts, sidx.offsets)
    return jnp.take_along_axis(granks, owners[None, :].astype(POS_DTYPE), axis=0)[0]


# ---------------------------------------------------------------------------
# shard_map paths: a2a exchange and allgather(psum) fallback
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("mesh", "axes", "backend", "cap"))
def _lookup_a2a(sidx: ShardedIndex, queries, mesh, axes, backend: str, cap: int):
    count_trace(f"sharded:{sidx.kind}", f"a2a:{backend}")
    count_u64_table("tier", sidx.tables)
    n_shards = sidx.n_shards
    ax = axes if len(axes) > 1 else axes[0]

    def block(idx, tab, cnt, off, fences, q):
        local = jax.tree_util.tree_map(lambda v: v[0], idx)
        b_loc = q.shape[0]
        owner = route_owners(fences, q)
        # bucket queries by owner into the capacity-factored request matrix
        req, slots, valid, order = collectives.bucket_by_owner(
            owner, q, n_shards, cap, jnp.zeros((), q.dtype)
        )
        # 1st all_to_all: requests travel to their owner shard
        req_x = lax.all_to_all(req, ax, split_axis=0, concat_axis=0, tiled=True)
        g = _answer_local(local, tab[0], cnt[0], off[0], req_x.reshape(-1), backend)
        # 2nd all_to_all: global ranks travel back to the requesters
        back = lax.all_to_all(g.reshape(n_shards, cap), ax, split_axis=0, concat_axis=0, tiled=True)
        # unsort; entries that never fit a slot keep the DROPPED sentinel
        return collectives.unbucket_inverse(back, slots, valid, order, b_loc, DROPPED)

    return jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(P(ax), P(ax), P(ax), P(ax), P(None), P(ax)),
        out_specs=P(ax),
        check_vma=False,
    )(sidx.index, sidx.tables, sidx.counts, sidx.offsets, sidx.fences, queries)


@partial(jax.jit, static_argnames=("mesh", "axes", "backend"))
def _lookup_allgather(sidx: ShardedIndex, queries, mesh, axes, backend: str):
    count_trace(f"sharded:{sidx.kind}", f"allgather:{backend}")
    count_u64_table("tier", sidx.tables)
    ax = axes if len(axes) > 1 else axes[0]

    def block(idx, tab, cnt, off, fences, q):
        local = jax.tree_util.tree_map(lambda v: v[0], idx)
        me = lax.axis_index(axes)
        owner = route_owners(fences, q)
        g = _answer_local(local, tab[0], cnt[0], off[0], q, backend)
        mine = owner.astype(jnp.int64) == me.astype(jnp.int64)
        return lax.psum(jnp.where(mine, g, jnp.zeros_like(g)), axes)

    return jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(P(ax), P(ax), P(ax), P(ax), P(None), P(None)),
        out_specs=P(None),
        check_vma=False,
    )(sidx.index, sidx.tables, sidx.counts, sidx.offsets, sidx.fences, queries)


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------

MODES = ("auto", "a2a", "allgather", "ref")

#: Backends the tier's local answer supports — the full ``Index.lookup``
#: set.  Under ``pallas`` the shard_map paths run each shard's fused
#: kernel on its resident block, and the vmapped fallback dispatches the
#: batched ``(table, q_tile)``-grid kernels across the whole tier.
TIER_BACKENDS = ("xla", "bbs", "pallas", "ref")


def sharded_lookup(
    sidx: ShardedIndex,
    queries,
    ctx=None,
    *,
    backend: str = "xla",
    mode: str = "auto",
    cap_factor: float = 2.0,
    telemetry: bool = False,
    telemetry_sink: dict | None = None,
    telemetry_label: str | None = None,
):
    """Predecessor ranks of ``queries`` against the whole sharded tier.

    ``ctx`` is a :class:`~repro.dist.sharding.ShardingCtx`; the tier is
    laid out over its ``tp`` logical axis.  ``mode``:

    * ``"a2a"`` — queries sharded over ``tp``, capacity-factored double
      ``all_to_all`` exchange (the scale path; see the module docstring
      for the overflow policy).
    * ``"allgather"`` — queries replicated, masked local answers merged
      with one ``psum`` (small-tier fallback, never drops).
    * ``"ref"`` — vmapped all-shards sweep, no collectives (single
      device or mesh/tier mismatch).
    * ``"auto"`` — ``a2a`` when the mesh's ``tp`` extent matches the
      shard count (>1), else ``ref``.

    Ranks are bit-identical to ``Index.lookup`` on the concatenated
    table, except over-capacity drops in ``a2a`` mode, which report
    :data:`DROPPED`.

    ``backend`` selects the per-shard answer path (any
    :data:`TIER_BACKENDS` entry): under ``"pallas"`` the shard_map
    modes run each shard's fused kernel on its resident block, and the
    vmapped fallback answers the whole tier with ONE batched
    ``(table, q_tile)``-grid kernel call.

    Example — a 4-shard PGM tier on a ``tp=4`` mesh::

        sidx = ShardedIndex.build("PGM", table, n_shards=4, eps=64)
        ctx = ShardingCtx(mesh=make_mesh((1, 4), ("data", "model")))
        ranks = sharded_lookup(sidx, queries, ctx, backend="pallas")
        # single-device fallback, still exact, no collectives:
        ranks = sharded_lookup(sidx, queries, mode="ref")

    ``telemetry=True`` additionally records per-call routing-imbalance
    and drop-rate counters into the ``repro.obs`` registry
    (:func:`tier_metrics` is the aggregate view) — one extra jitted
    owner histogram plus a host sync, so serving loops opt in and
    benchmarks stay untouched.  ``telemetry_label`` attributes the same
    counters to a per-tier ``route_*`` labelset when one process serves
    several tiers (the ``tier="all"`` aggregate always updates);
    ``telemetry_sink`` (a counter dict in :func:`_fresh_tier_metrics`
    shape) is the legacy dict-based attribution and receives the same
    updates.
    """
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; choose from {MODES}")
    if backend not in TIER_BACKENDS:
        raise ValueError(f"unknown tier backend {backend!r}; choose from {TIER_BACKENDS}")
    from repro.index.impls import query_impl

    kind_backends = query_impl(sidx.kind).backends
    if backend not in kind_backends:
        raise ValueError(
            f"kind {sidx.kind!r} supports backends {kind_backends}, not {backend!r}"
        )
    queries = jnp.asarray(queries)
    if queries.ndim != 1:
        raise ValueError("sharded_lookup expects a flat (B,) query vector")
    n_shards = sidx.n_shards
    tp = ctx.n("tp") if ctx is not None else 1
    axes = ctx.mesh_axes("tp") if ctx is not None else ()
    spmd_ok = tp == n_shards and n_shards > 1 and bool(axes)
    if mode == "auto":
        mode = "a2a" if spmd_ok else "ref"
    if mode in ("a2a", "allgather") and not spmd_ok:
        raise ValueError(
            f"mode={mode!r} needs the mesh tp extent ({tp}) to equal n_shards "
            f"({n_shards}); use mode='ref' or 'auto'"
        )
    if mode == "ref":
        out = _lookup_vmapped(sidx, queries, backend)
    elif mode == "allgather":
        out = _lookup_allgather(sidx, queries, ctx.mesh, axes, backend)
    else:
        b = queries.shape[0]
        pad = (-b) % n_shards
        padded = (
            jnp.concatenate([queries, jnp.zeros((pad,), queries.dtype)]) if pad else queries
        )
        b_loc = padded.shape[0] // n_shards
        cap = collectives.exchange_capacity(b_loc, n_shards, cap_factor)
        out = _lookup_a2a(sidx, padded, ctx.mesh, axes, backend, cap)
        out = out[:b] if pad else out
    if telemetry:
        _record_tier_metrics(sidx, queries, out, telemetry_sink, telemetry_label)
    return out


# ---------------------------------------------------------------------------
# Donated in-place refresh
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("shard",), donate_argnums=(0,))
def _install_shard(sidx: ShardedIndex, new_arrays, new_table, new_fence, new_count, shard: int):
    arrays = {k: v.at[shard].set(new_arrays[k]) for k, v in sidx.index.arrays.items()}
    counts = sidx.counts.at[shard].set(new_count)
    offsets = jnp.concatenate([jnp.zeros((1,), POS_DTYPE), jnp.cumsum(counts)[:-1]])
    return ShardedIndex(
        index=Index(sidx.index.kind, sidx.index.static, arrays),
        tables=sidx.tables.set_row(shard, new_table),
        fences=sidx.fences.at[shard].set(new_fence),
        counts=counts,
        offsets=offsets,
    )


def refresh_shard(sidx: ShardedIndex, shard: int, new_index: Index, new_table) -> ShardedIndex:
    """Swap a rebuilt shard into the tier without host round-trips.

    The old stacked pytree is *donated* to a jitted ``.at[shard].set``
    update, so the swap reuses the resident buffers instead of copying
    the whole tier through the host; offsets are recomputed on device
    (a rebuilt shard may change its key count).

    ``new_index`` must be built with a shard-stable spec: structural
    statics must match the tier and its (padded) leaves must fit the
    stacked leaf shapes.  ``new_table`` is the shard's raw (unpadded)
    sorted key array — but the *index* must be fitted on
    :func:`shard_build_table` of it: static kinds normalise predictions
    by the lookup-time table length, so an index fitted on the raw keys
    answers wrongly against the padded resident row whenever
    ``len(new_table) < m`` (exact-power-of-two shards mask this).
    """
    if new_index.kind != sidx.index.kind:
        raise ValueError(f"kind mismatch: tier is {sidx.index.kind!r}, got {new_index.kind!r}")
    if registry.entry(new_index.kind).query_key == "pgm":
        if new_index.s("levels") < sidx.index.s("levels"):
            new_index = _lift_pgm_levels(new_index, sidx.index.s("levels"))
    for (name, have), (n2, new) in zip(sidx.index.static, new_index.static):
        if name != n2:
            raise ValueError("static key mismatch between tier and rebuilt shard")
        if name in _STEP_KEYS:
            if new > have:
                raise ValueError(
                    f"rebuilt shard needs {name}={new} > tier's {have}: restack the tier "
                    "(a larger trip count cannot be installed without a retrace)"
                )
        elif new != have:
            raise ValueError(f"static {name!r} mismatch: tier {have}, rebuilt shard {new}")
    new_table = np.asarray(new_table, dtype=np.uint64)
    if len(new_table) == 0:
        raise ValueError("cannot install an empty shard")
    m = int(sidx.tables.shape[1])
    if len(new_table) > m:
        raise ValueError(f"rebuilt shard has {len(new_table)} keys > tier table capacity {m}")
    # the rebuilt key set must stay inside this shard's fence slot, or
    # global ranks would silently go wrong for every later shard
    if shard > 0:
        prev_last = np.uint64(np.asarray(sidx.tables[shard - 1, int(sidx.counts[shard - 1]) - 1]))
        if new_table[0] <= prev_last:
            raise ValueError(
                f"rebuilt shard {shard} starts at {new_table[0]}, inside the previous "
                f"shard's range (its last key is {prev_last})"
            )
    if shard + 1 < sidx.n_shards:
        next_fence = np.uint64(sidx.fences[shard + 1])
        if new_table[-1] >= next_fence:
            raise ValueError(
                f"rebuilt shard {shard} ends at {new_table[-1]}, at or beyond the next "
                f"shard's fence {next_fence}"
            )
    padded_tab = LimbTable.from_u64(_pad_sorted_table(new_table, m))
    if sidx.index.kind == "GAPPED":
        # inert zero-count leaf rows, not the generic edge-replication pad
        new_index = _pad_gapped_leaves(new_index, int(sidx.index.arrays["keys"].shape[1]))
    new_arrays = {}
    for k, v in sidx.index.arrays.items():
        if k not in new_index.arrays:
            raise ValueError(f"rebuilt shard is missing leaf {k!r}")
        new_arrays[k] = jnp.asarray(_pad_to(np.asarray(new_index.arrays[k]), v.shape[1:]))
    return _install_shard(
        sidx,
        new_arrays,
        padded_tab,
        jnp.asarray(new_table[0], jnp.uint64),
        jnp.asarray(len(new_table), POS_DTYPE),
        shard,
    )


# ---------------------------------------------------------------------------
# Skew-aware rebalancing: weighted-quantile fences + ordered re-shard
# ---------------------------------------------------------------------------


def shard_build_table(kind: str, part: np.ndarray, m: int) -> np.ndarray:
    """The table a replacement shard index must be *fitted* on to be
    installable at stacked capacity ``m`` (mirrors
    :meth:`ShardedIndex.build`): static kinds fit on the padded table —
    their query paths normalise model predictions by the lookup-time
    table length, which is the resident padded row — while
    self-contained kinds (GAPPED) own their keys and fit on the raw
    part so a pad key can never become live.  Raises ``ValueError``
    when ``part`` no longer fits ``m`` (the restack cue)."""
    from repro.index.impls import query_impl

    part = np.asarray(part, dtype=np.uint64)
    if query_impl(kind).lookup is not None:
        return part
    return _pad_sorted_table(part, m)


def weighted_quantile_bounds(merged_keys, fences, weights) -> np.ndarray:
    """Rank partition of ``merged_keys`` that evens out *observed* load.

    The per-shard query counts ``weights`` (one per current fence slot)
    define a piecewise-constant traffic density over the sorted global
    key set: every key in current shard ``s`` carries ``weights[s]``
    spread evenly over that shard's keys.  Inverting the cumulative
    weight at ``j/S`` for ``j = 1..S-1`` yields new shard bounds under
    which each shard would have answered an equal share of the observed
    traffic — the weighted-quantile split of the ISSUE/ROADMAP item.

    Degenerate inputs stay well-formed: an all-zero weight vector falls
    back to the even split, and the bounds are clamped to a strictly
    increasing partition with at least one key per shard (``refresh_shard``
    rejects empty shards).  Keys outside the current fence range (e.g.
    pending inserts below the global min) attach to the nearest shard.
    """
    merged = np.asarray(merged_keys, dtype=np.uint64)
    fences = np.asarray(fences, dtype=np.uint64)
    w = np.asarray(weights, dtype=np.float64).reshape(-1)
    n, S = len(merged), len(fences)
    if len(w) != S:
        raise ValueError(f"got {len(w)} weights for {S} fence slots")
    if n < S:
        raise ValueError(f"cannot split {n} keys across {S} shards")
    own = np.clip(np.searchsorted(fences, merged, side="right") - 1, 0, S - 1)
    per_owner = np.bincount(own, minlength=S).astype(np.float64)
    if w.sum() <= 0:
        w = np.ones(S, dtype=np.float64)
    # a shard that owns no current keys contributes no density rows;
    # spread every observed weight over its owner's resident keys
    per_key = np.where(per_owner[own] > 0, w[own] / np.maximum(per_owner[own], 1.0), 0.0)
    if per_key.sum() <= 0:
        per_key = np.ones(n, dtype=np.float64)
    cum = np.cumsum(per_key)
    targets = cum[-1] * np.arange(1, S, dtype=np.float64) / S
    inner = np.searchsorted(cum, targets, side="left") + 1
    # clamp to a strictly increasing partition with >= 1 key per shard
    for j in range(len(inner)):
        lo = (inner[j - 1] + 1) if j else 1
        inner[j] = max(int(inner[j]), lo)
    for j in range(len(inner) - 1, -1, -1):
        hi = (inner[j + 1] - 1) if j + 1 < len(inner) else n - 1
        inner[j] = min(int(inner[j]), hi)
    return np.concatenate([[0], inner, [n]]).astype(np.int64)


def rebalance_shards(sidx: ShardedIndex, merged_keys, bounds, build_shard) -> ShardedIndex:
    """Repartition the tier at ``bounds`` over the global sorted key set
    via the existing donated ``refresh_shard`` swaps — no restack, no
    host-side re-stacking of untouched leaves.

    Each boundary move creates an install-order dependency only between
    the two adjacent shards (``refresh_shard`` validates the new shard
    against the *current* neighbours: a boundary moving right means the
    right shard must shrink before the left can grow, and vice versa), so
    the dependency graph is an acyclically oriented path and a simple
    deferred-retry sweep always terminates in <= ``n_shards`` rounds.
    Raises ``ValueError`` when a rebuilt shard cannot be installed at all
    (e.g. it outgrew the stacked table capacity) — the caller's cue to
    fall back to ``ShardedIndex.build(..., bounds=...)``.

    ``build_shard(build_table)`` builds the per-shard index for a key
    slice already run through :func:`shard_build_table` (the tier passes
    its pinned spec, keeping rebalances retune-free).  Every shard is
    built — and capacity-checked — *before* the first donated install,
    so a non-installable partition fails with the old tier intact.
    """
    merged = np.asarray(merged_keys, dtype=np.uint64)
    bounds = np.asarray(bounds, dtype=np.int64).reshape(-1)
    S = sidx.n_shards
    if len(bounds) != S + 1 or bounds[0] != 0 or bounds[-1] != len(merged):
        raise ValueError(
            f"bounds must partition [0, {len(merged)}] into {S} shards, got {bounds.tolist()}"
        )
    if (np.diff(bounds) < 1).any():
        raise ValueError(f"bounds must give every shard >= 1 key, got {bounds.tolist()}")
    m = int(sidx.tables.shape[1])
    kind = sidx.index.kind
    parts = [merged[bounds[s] : bounds[s + 1]] for s in range(S)]
    built = [build_shard(shard_build_table(kind, p, m)) for p in parts]
    remaining = set(range(S))
    while remaining:
        progressed = False
        last_err: Exception | None = None
        for s in sorted(remaining):
            try:
                sidx = refresh_shard(sidx, s, built[s], parts[s])
            except ValueError as e:
                last_err = e
                continue
            remaining.discard(s)
            progressed = True
        if not progressed:
            raise ValueError(f"rebalance not installable via refresh_shard: {last_err}")
    return sidx


# ---------------------------------------------------------------------------
# Donated in-place shard mutation (updatable kinds: GAPPED)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("shard",), donate_argnums=(0,))
def _install_mutated(sidx: ShardedIndex, new_arrays, new_fence, new_count, shard: int):
    arrays = {k: v.at[shard].set(new_arrays[k]) for k, v in sidx.index.arrays.items()}
    counts = sidx.counts.at[shard].set(new_count)
    offsets = jnp.concatenate([jnp.zeros((1,), POS_DTYPE), jnp.cumsum(counts)[:-1]])
    return ShardedIndex(
        index=Index(sidx.index.kind, sidx.index.static, arrays),
        tables=sidx.tables,
        fences=sidx.fences.at[shard].set(new_fence),
        counts=counts,
        offsets=offsets,
    )


def insert_into_shard(sidx: ShardedIndex, shard: int, keys, *, auto_compact: bool = True):
    """Absorb a key batch into one *updatable* shard without rebuilding.

    The shard's sliced :class:`~repro.index.Index` view runs the kind's
    registered ``insert_batch`` mutator (gap absorption first, delta
    overflow second — see :mod:`repro.index.mutation`), and the mutated
    leaves are swapped back with a donated ``.at[shard].set`` update that
    also keeps ``counts``/``offsets``/``fences`` in sync with the
    shard's *live* key set.  ``sidx.tables`` is left untouched: for
    self-contained kinds the lookup ignores it, and it becomes a stale
    build-time snapshot (use :func:`repro.index.updatable.live_keys` on
    ``sidx.shard(s)`` to read the live keys).

    Returns ``(new_sidx, InsertReport)``.  Raises ``TypeError`` for
    static kinds and :class:`repro.index.mutation.NeedsRebuild` when the
    shard's fixed capacity is exhausted — the caller's cue to rebuild
    the shard via :func:`refresh_shard` (see
    :meth:`repro.tune.rebuild.TunedTier.insert_batch`).
    """
    from repro.index import mutation

    if not 0 <= shard < sidx.n_shards:
        raise ValueError(f"shard {shard} out of range [0, {sidx.n_shards})")
    keys = np.asarray(keys, dtype=np.uint64).reshape(-1)
    if keys.size and shard + 1 < sidx.n_shards:
        # fence discipline: a key at/beyond the next fence belongs to a
        # later shard — absorbing it here would corrupt global ranks
        next_fence = np.uint64(sidx.fences[shard + 1])
        if keys.max() >= next_fence:
            raise ValueError(
                f"key {int(keys.max())} at/beyond shard {shard}'s next fence "
                f"{int(next_fence)}: route keys with route_owners first"
            )
    new_local, report = mutation.insert_batch(
        sidx.shard(shard), keys, auto_compact=auto_compact
    )
    new_count = int(sidx.counts[shard]) + report.absorbed + report.overflowed
    new_sidx = _install_mutated(
        sidx,
        new_local.arrays,
        new_local.arrays["fences"][0],
        jnp.asarray(new_count, POS_DTYPE),
        shard,
    )
    return new_sidx, report


def compact_shard(sidx: ShardedIndex, shard: int) -> ShardedIndex:
    """Fold one updatable shard's delta buffer into its leaves in place
    (device-side compaction + donated swap; the live key set — and so
    ``counts``/``offsets`` — is unchanged).  Raises ``NeedsRebuild``
    when the live set no longer fits the shard's leaves."""
    from repro.index import mutation

    if not 0 <= shard < sidx.n_shards:
        raise ValueError(f"shard {shard} out of range [0, {sidx.n_shards})")
    new_local = mutation.compact(sidx.shard(shard))
    return _install_mutated(
        sidx,
        new_local.arrays,
        new_local.arrays["fences"][0],
        sidx.counts[shard],
        shard,
    )
