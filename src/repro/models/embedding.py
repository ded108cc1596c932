"""Sharded embedding substrate for the recsys architectures.

JAX has no EmbeddingBag or giant-table primitive; this module builds
both from scratch (kernel_taxonomy §RecSys):

* :func:`sharded_lookup` — rows of each table sharded over the whole
  mesh.  Two modes, selectable per config (the §Perf hillclimb target):
    - ``allreduce``: every shard masked-gathers its local rows and the
      partial results are psummed (simple; collective = batch x dim x
      n_fields floats).
    - ``a2a``: requests are bucketed to owner shards via shard_map +
      all_to_all (collective = only the vectors actually needed).
* :class:`LearnedKeyedEmbedding` — the paper's technique on the hottest
  path: raw 64-bit hashed ids are looked up in a *compressed sorted key
  table* via an RMI/PGM learned index instead of allocating dense
  hash-space tables (DESIGN.md §3, integration point 1).
* :func:`embedding_bag` — take + segment_sum (the XLA path; the Pallas
  one-hot-matmul kernel covers the VMEM-resident tier).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


def embedding_bag(table, ids, seg_ids, num_bags: int, weights=None):
    """EmbeddingBag via take + segment_sum (sum mode)."""
    vecs = jnp.take(table, ids, axis=0)
    if weights is not None:
        vecs = vecs * weights[:, None]
    return jax.ops.segment_sum(vecs, seg_ids, num_segments=num_bags)


def sharded_lookup(table, ids, ctx, mode: str = "allreduce", cap_factor: float = 2.0):
    """ids (B, F) int32 rows into ``table`` (V, D) row-sharded over mesh.

    Returns (B, F, D).  ``allreduce``: local masked gather + psum.
    ``a2a``: shard_map all_to_all exchange of (id -> vector) requests,
    capacity-bounded at ``cap_factor`` x the per-shard average (skewed
    ids beyond capacity are dropped to the zero vector — the standard
    bounded-exchange contract; raise cap_factor for exactness).
    """
    if mode == "allreduce":
        # XLA's SPMD partitioner turns the gather-from-row-sharded into
        # exactly the masked-gather+psum pattern under these constraints.
        table = ctx.constrain(table, "row", None)
        out = jnp.take(table, ids, axis=0)
        return ctx.constrain(out, "dp", None, None)

    if mode == "a2a":
        b = ids.shape[0]
        dp = ctx.n("dp")
        pad = (-b) % dp
        if pad:
            ids = jnp.concatenate([ids, jnp.zeros((pad,) + ids.shape[1:], ids.dtype)])
        out = _a2a_lookup(table, ids, ctx, cap_factor)
        return out[:b] if pad else out
    raise ValueError(mode)


def _a2a_lookup(table, ids, ctx, cap_factor: float = 2.0):
    """Owner-exchange lookup via shard_map over the flattened mesh.

    Each shard owns a contiguous row range.  Every shard sends each of
    its local ids to the owner (all_to_all), owners gather locally and
    the vectors return (second all_to_all).  Collective bytes = the
    vectors actually requested (vs the psum of full batch in allreduce
    mode).
    """
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    axes = tuple(mesh.axis_names)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    v, d = table.shape
    b, f = ids.shape
    rows_per = v // n_shards
    dp_axes = ctx.rules["dp"] or ()

    def block(tab, local_ids):
        from repro.dist import collectives

        # tab: (rows_per, D); local_ids: (B_loc, F)
        flat = local_ids.reshape(-1).astype(jnp.int64)  # (N,)
        n = flat.shape[0]
        owner = jnp.clip(flat // rows_per, 0, n_shards - 1)
        # bucket ids by owner shard into the capacity-bounded request matrix
        cap = collectives.exchange_capacity(n, n_shards, cap_factor)
        req, slots, valid, order = collectives.bucket_by_owner(
            owner, flat, n_shards, cap, jnp.zeros((), flat.dtype)
        )

        # 1st all_to_all: requests travel to their owner shard
        req_x = _all_to_all_flat(req, axes)  # (n_shards, cap) ids this shard owns
        local_rows = jnp.clip(
            req_x - _shard_offset(axes, rows_per), 0, rows_per - 1
        ).astype(jnp.int32)
        vecs = jnp.take(tab, local_rows.reshape(-1), axis=0).reshape(n_shards, cap, d)
        # 2nd all_to_all: vectors travel back to the requesters
        vecs_back = _all_to_all_flat(vecs, axes)

        # scatter vectors back to input order (over-capacity -> zero vector)
        out = collectives.unbucket_inverse(vecs_back, slots, valid, order, n, 0)
        return out.reshape(local_ids.shape[0], f, d)

    dp_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    return jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(P(axes, None), P(dp_spec, None)),
        out_specs=P(dp_spec, None, None),
        check_vma=False,
    )(table, ids)


def _dp_size(ctx):
    return ctx.n("dp")


def _shard_offset(axes, rows_per):
    idx = lax.axis_index(axes)
    return (idx * rows_per).astype(jnp.int64)


def _all_to_all_flat(x, axes):
    """all_to_all over the flattened mesh axes: x (n_shards, ...) swaps
    the leading chunk axis with the shard axis."""
    return lax.all_to_all(x, axes, split_axis=0, concat_axis=0, tiled=True)


@dataclass
class LearnedKeyedEmbedding:
    """Compressed-vocabulary embedding keyed by a learned index.

    Production recsys ids are 64-bit hashes; a dense table over the hash
    space is impossible and hashing-by-modulo collides.  Here the *sorted
    unique key set* (built offline) is searched with the paper's learned
    index to map raw id -> dense row — predecessor search on the hot
    path (the id-translation step).

    Built with ``n_shards > 1`` and a :class:`~repro.dist.ShardingCtx`,
    the key set is partitioned into a :class:`~repro.dist.ShardedIndex`
    tier and id translation runs through the shard_map'd
    :func:`repro.dist.sharded_lookup` (fence-route-answer-return) before
    the vector gather.
    """

    keys: jnp.ndarray  # (V,) uint64 sorted unique raw ids
    table: jnp.ndarray  # (V+1, D) f32 — last row is the OOV vector
    index: object = None  # repro.index.Index over ``keys`` (unsharded tier)
    sharded: object = None  # repro.dist.ShardedIndex tier (n_shards > 1)
    ctx: object = None  # ShardingCtx the tier is laid out on
    cap_factor: float = 0.0  # 0 -> n_shards (exchange can never drop)

    @staticmethod
    def build(
        raw_keys: np.ndarray,
        dim: int,
        seed: int = 0,
        b: int | None = None,
        *,
        kind: str = "RMI",
        ctx=None,
        n_shards: int = 1,
        **params,
    ):
        from repro import index as ix

        keys = np.unique(raw_keys.astype(np.uint64))
        v = len(keys)
        rng = np.random.default_rng(seed)
        table = (rng.normal(0, 0.05, size=(v + 1, dim))).astype(np.float32)
        if kind.upper() == "RMI" and "b" not in params:
            params["b"] = b or max(2, v // 128)
        index = sharded = None
        if n_shards > 1:
            from repro.dist.sharded_index import ShardedIndex

            sharded = ShardedIndex.build(kind, keys, n_shards=n_shards, **params)
        else:
            index = ix.build(kind, keys, **params)
        return LearnedKeyedEmbedding(
            keys=jnp.asarray(keys),
            table=jnp.asarray(table),
            index=index,
            sharded=sharded,
            ctx=ctx,
        )

    def translate(self, raw_ids, *, backend: str = "xla"):
        """Raw 64-bit ids -> predecessor ranks in the sorted key set."""
        qf = jnp.asarray(raw_ids, dtype=jnp.uint64).reshape(-1)
        if self.sharded is not None:
            from repro.dist.sharded_index import sharded_lookup as tier_lookup

            cap = self.cap_factor or float(self.sharded.n_shards)
            return tier_lookup(self.sharded, qf, self.ctx, backend=backend, cap_factor=cap)
        return self.index.lookup(self.keys, qf, backend=backend)

    def lookup(self, raw_ids, *, backend: str = "xla"):
        q = jnp.asarray(raw_ids, dtype=jnp.uint64)
        shape = q.shape
        qf = q.reshape(-1)
        rank = self.translate(qf, backend=backend)
        # misses (no exact key, capacity drops) fall through to OOV
        hit = (rank >= 0) & (jnp.take(self.keys, jnp.maximum(rank, 0)) == qf)
        v = self.table.shape[0] - 1
        row = jnp.where(hit, jnp.maximum(rank, 0), v)  # miss -> OOV row
        out = jnp.take(self.table, row, axis=0)
        return out.reshape(*shape, -1)
