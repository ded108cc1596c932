r"""The :class:`Index` pytree and the shared jitted query path.

The paper's view — and Kraska et al.'s — is that a learned index *is
data*: a handful of flat arrays (segments, fences, slopes, intercepts)
driven by one generic lookup procedure.  ``Index`` realises that view as
a registered JAX pytree:

* **leaves** — the model's arrays (``index.arrays``), so an ``Index``
  can be passed through ``jax.jit``, ``vmap``, donated, sharded, or
  serialized like any other pytree of arrays;
* **treedef aux** — the kind tag plus a small tuple of static ints
  (loop trip counts, level counts), deliberately log-bucketed so that
  different instances of a kind collide onto the *same* jit cache entry.

Because the model is an argument rather than a closure constant, there
is exactly **one** jitted query function per (kind, backend) — building
ten SY-RMIs at ten space budgets re-traces zero to one times instead of
ten.  ``trace_counts()`` exposes the cache behaviour for tests and
benchmarks.

Backends (``lookup(..., backend=...)``):

* ``"xla"``    — intervals + branch-free bounded search (default);
* ``"bbs"``    — intervals + branchy early-exit epilogue (paper's \*-BBS);
* ``"pallas"`` — fused Pallas kernels for the learned-model families
  (RMI/SY-RMI predict+search, PGM descent, RadixSpline radix+knot+probe)
  and the lane-wide k-ary kernel for the model-free kinds (atomic / KO /
  B+-tree); interpret mode off-TPU.  Batched/tier lookups dispatch the
  ``(table, q_tile)``-grid batched kernel variants via
  :func:`batched_pallas_impl`;
* ``"ref"``    — ``jnp.searchsorted`` oracle (parity testing).
"""

from __future__ import annotations

import collections
import json
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.cdf import POS_DTYPE
from repro.core.limbs import LimbTable

BACKENDS = ("xla", "bbs", "pallas", "ref")

_TRACE_COUNTS: collections.Counter = collections.Counter()

#: lookup programs traced with a u64 table operand, by program
#: (``index``: ``_lookup_jit``; ``tier``: the sharded tier's lookups)
_U64_TABLE_TRACES: collections.Counter = collections.Counter({"index": 0, "tier": 0})


def trace_counts() -> dict:
    """(kind, backend) -> number of times the shared lookup was traced."""
    return dict(_TRACE_COUNTS)


def u64_table_traces() -> dict:
    """program -> lookup traces whose table operand was a u64 array, which
    XLA splits whole into u32 limbs on every call (a
    :class:`~repro.core.limbs.LimbTable` operand is not counted).  Mirrored
    into the ``lookup_u64_table_traces`` obs counter at snapshot time."""
    return dict(_U64_TABLE_TRACES)


def reset_trace_counts() -> None:
    _TRACE_COUNTS.clear()


class Index:
    """A learned static index as a pytree of flat arrays.

    Attributes
    ----------
    kind:    registry kind tag (``"RMI"``, ``"PGM"``, ...) — static.
    static:  tuple of ``(name, int)`` pairs — static query metadata
             (bucketed loop trip counts, level counts, degrees).
    arrays:  dict name -> jnp.ndarray — the pytree leaves.
    info:    host-side build metadata (name, build_time, eps, ...).
             *Not* part of the pytree: it is dropped under tracing and
             by ``tree_unflatten`` so it can never fragment jit caches.
    """

    __slots__ = ("kind", "static", "arrays", "info")

    def __init__(self, kind: str, static: tuple, arrays: dict, info: dict | None = None):
        object.__setattr__(self, "kind", kind)
        object.__setattr__(self, "static", tuple(static))
        object.__setattr__(self, "arrays", dict(arrays))
        object.__setattr__(self, "info", dict(info or {}))

    # -- static metadata --------------------------------------------------
    def s(self, name: str) -> int:
        for k, v in self.static:
            if k == name:
                return v
        raise KeyError(name)

    @property
    def name(self) -> str:
        return self.info.get("name", self.kind)

    def __getattr__(self, item):
        # convenience passthrough: idx.eps, idx.b, idx.n_segments_l0, ...
        info = object.__getattribute__(self, "info")
        if item in info:
            return info[item]
        raise AttributeError(item)

    def __repr__(self):
        shapes = {k: tuple(v.shape) for k, v in self.arrays.items()}
        return f"Index(kind={self.kind!r}, static={dict(self.static)}, arrays={shapes})"

    # -- pytree protocol --------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.arrays))
        children = tuple(self.arrays[k] for k in names)
        return children, (self.kind, self.static, names)

    @classmethod
    def tree_unflatten(cls, aux, children):
        kind, static, names = aux
        return cls(kind, static, dict(zip(names, children)), info=None)

    # -- queries ----------------------------------------------------------
    def intervals(self, table, queries):
        """Predicted inclusive window [lo, hi] per query (jittable)."""
        from . import impls

        return impls.query_impl(self.kind).intervals(self, table, queries)

    def backends(self) -> tuple:
        """The backends this kind supports (subset of :data:`BACKENDS`)."""
        from . import impls

        return impls.query_impl(self.kind).backends

    def lookup(self, table, queries, *, backend: str = "xla"):
        """Predecessor ranks through the shared jitted query path."""
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; choose from {BACKENDS}")
        if backend not in self.backends():
            raise ValueError(
                f"kind {self.kind!r} supports backends {self.backends()}, not {backend!r}"
            )
        return _lookup_jit(self, jnp.asarray(table), jnp.asarray(queries), backend)

    def predecessor(self, table, queries, *, branchy: bool = False, backend: str | None = None):
        r"""Predecessor ranks; ``branchy=True`` selects the \*-BBS epilogue."""
        return self.lookup(table, queries, backend=backend or ("bbs" if branchy else "xla"))

    # -- mutation (updatable kinds only) ----------------------------------
    def insert_batch(self, keys, *, auto_compact: bool = True):
        """Insert a batch of keys (updatable kinds, e.g. ``GAPPED``).

        Returns ``(new_index, InsertReport)`` — absorption into leaf gaps
        first, overflow to the delta buffer, ``auto_compact`` folding the
        delta into the leaves when it would overflow.  Static kinds raise
        ``TypeError``; see :mod:`repro.index.mutation`.
        """
        from . import mutation

        return mutation.insert_batch(self, keys, auto_compact=auto_compact)

    def compact(self) -> "Index":
        """Fold the delta buffer into the gapped leaves (device-side)."""
        from . import mutation

        return mutation.compact(self)

    # -- accounting / serialization --------------------------------------
    def space_bytes(self) -> int:
        """Model space in the paper's sense: the bytes of the leaves that
        constitute the model (valid prefixes of padded leaves; query-time
        caches like the fused kernel's f32 re-encoding excluded)."""
        from . import impls

        return impls.query_impl(self.kind).space_bytes(self)

    def nbytes(self) -> int:
        """Total resident bytes of every pytree leaf as stored (padding
        and kernel re-encodings included) — ``space_bytes`` <= this."""
        return sum(int(v.nbytes) for v in self.arrays.values())

    def save(self, path) -> None:
        """npz round-trip: arrays bit-exact, kind/static/info as JSON."""
        payload = {f"arr_{k}": np.asarray(v) for k, v in self.arrays.items()}
        meta = {
            "kind": self.kind,
            "static": list(map(list, self.static)),
            "info": {k: v for k, v in self.info.items() if isinstance(v, (str, int, float, bool))},
        }
        payload["__meta__"] = np.frombuffer(json.dumps(meta).encode(), dtype=np.uint8)
        np.savez(path, **payload)

    @classmethod
    def load(cls, path) -> "Index":
        with np.load(path) as z:
            meta = json.loads(bytes(z["__meta__"]).decode())
            arrays = {
                k[len("arr_"):]: jnp.asarray(z[k]) for k in z.files if k.startswith("arr_")
            }
        static = tuple((k, int(v)) for k, v in meta["static"])
        return cls(meta["kind"], static, arrays, info=meta.get("info"))


jax.tree_util.register_pytree_node_class(Index)


# ---------------------------------------------------------------------------
# The shared jitted query path: ONE trace per (kind structure, backend)
# ---------------------------------------------------------------------------


def lookup_impl(index: Index, table, queries, backend: str):
    """Traceable body of the shared lookup (no jit wrapper of its own).

    Composite query paths — the shard_map'd sharded lookup, vmapped
    multi-index sweeps — call this inside their *own* single jitted
    function instead of nesting ``Index.lookup``'s jit, so they keep the
    one-trace-per-kind guarantee."""
    from . import impls

    impl = impls.query_impl(index.kind)

    if impl.lookup is not None:
        # self-contained kinds (GAPPED two-tier merge): the index owns its
        # keys, so the answer ignores ``table`` on every backend
        return impl.lookup(index, table, queries, backend)
    if backend == "ref":
        # the oracle: a limb table is combined into u64 keys in the program
        keys = table.combine() if isinstance(table, LimbTable) else table
        return jnp.searchsorted(keys, queries, side="right").astype(POS_DTYPE) - 1
    if backend == "pallas":
        return impl.pallas(index, table, queries)

    # named scopes are op metadata only: the device trace attributes each
    # op to predict or search, and the jitted program keeps its name
    with jax.named_scope("predict"):
        lo, hi = impl.intervals(index, table, queries)
    from repro.core import search

    with jax.named_scope("search"):
        if backend == "bbs":
            return search.bounded_bbs_branchy(table, queries, lo, hi)
        return search.bounded_bfs(table, queries, lo, hi, max_window=1 << impl.epi_steps(index))


def batched_pallas_impl(index: Index, tables, queries):
    """Traceable batched-Pallas lookup body: ``(n_tables, B)`` raw local
    predecessor ranks for stacked leaves / tables / queries.

    The ``backend="pallas"`` counterpart of ``vmap``-over-
    :func:`lookup_impl`: instead of vmapping the single-table kernels,
    it dispatches the kind's batched kernel (fused RMI with a
    ``(table, q_tile)`` grid; batched lane-wide k-ary otherwise), so a
    whole tier/batch is one ``pallas_call``.  Callers own the valid-count
    clamp and any rank rebasing, exactly as with ``vmap``'d
    ``lookup_impl`` — see ``BatchedIndexes.lookup`` and the sharded
    tier's fallback path.
    """
    from . import impls

    return impls.query_impl(index.kind).pallas_batched(index, tables, queries)


def count_trace(kind: str, backend: str) -> None:
    """Record one trace of a shared query path (python side effect: call
    it from *inside* a jitted function so it fires once per trace)."""
    _TRACE_COUNTS[(kind, backend)] += 1


def count_u64_table(program: str, table) -> None:
    """Record, at trace time like :func:`count_trace`, that ``program``
    was traced with a u64 table operand (see :func:`u64_table_traces`)."""
    if getattr(table, "dtype", None) == jnp.uint64:
        _U64_TABLE_TRACES[program] += 1


@partial(jax.jit, static_argnames=("backend",))
def _lookup_jit(index: Index, table, queries, backend: str):
    count_trace(index.kind, backend)  # python side effect: runs per trace
    count_u64_table("index", table)
    return lookup_impl(index, table, queries, backend)


def build(kind_or_spec, table_np, **params) -> Index:
    """Build an :class:`Index` from a spec (or kind string + params)."""
    from . import registry
    from .specs import IndexSpec

    if isinstance(kind_or_spec, IndexSpec):
        spec = kind_or_spec
    else:
        spec = registry.spec_for(str(kind_or_spec), **params)
    return registry.entry(spec.kind).build(spec, table_np)
