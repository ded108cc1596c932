"""repro.index — the unified public API for learned static indexes.

Design (the math lives in :mod:`repro.core`; this package owns the
public API):

* **Specs** (:mod:`~repro.index.specs`): one hashable frozen dataclass
  per kind describes *how to build* an index — nothing else.
* **Registry** (:mod:`~repro.index.registry`): kinds register once, in
  the paper's hierarchy order, via a decorator; ``kinds()`` is the only
  source of truth for the kind list.
* **Index** (:mod:`~repro.index.index`): the built artifact — a
  registered JAX pytree whose leaves are the model's flat arrays, so
  indexes can flow through jit/vmap/shard/donate and serialize via
  ``save``/``load`` npz round-trips.
* **Backends**: ``lookup(table, queries, backend="xla"|"bbs"|"pallas"|
  "ref")`` — one shared jitted query path per kind; the Pallas fast
  path's f32/i32 re-encoding is folded into build.  Batched/tier
  lookups dispatch
  through :func:`batched_pallas_impl` to the fused ``(table, q_tile)``-
  grid kernels — RMI, PGM and RS families each answer a whole batch
  with ONE ``pallas_call``; the model-free kinds use the batched k-ary
  kernel.

Quick start::

    from repro.index import Index, RMISpec, build
    idx = build(RMISpec(b=2048), table)     # or build("RMI", table, b=2048)
    ranks = idx.lookup(table, queries)      # shared jit: no per-model trace
    idx.save("rmi.npz"); idx2 = Index.load("rmi.npz")
"""

from .index import (
    BACKENDS,
    Index,
    batched_pallas_impl,
    build,
    count_trace,
    count_u64_table,
    lookup_impl,
    reset_trace_counts,
    trace_counts,
    u64_table_traces,
)
from .mutation import InsertReport, NeedsRebuild, updatable_kinds
from .registry import entry, kinds, spec_for
from .specs import (
    AtomicSpec,
    BTreeSpec,
    GappedSpec,
    IndexSpec,
    KOSpec,
    PGMBicriteriaSpec,
    PGMSpec,
    RMISpec,
    RSSpec,
    SYRMISpec,
)
from . import impls as _impls  # noqa: F401  — populates the registry
from . import updatable as _updatable  # noqa: F401  — registers GAPPED

__all__ = [
    "BACKENDS",
    "Index",
    "batched_pallas_impl",
    "build",
    "count_trace",
    "count_u64_table",
    "lookup_impl",
    "trace_counts",
    "reset_trace_counts",
    "u64_table_traces",
    "entry",
    "kinds",
    "spec_for",
    "IndexSpec",
    "AtomicSpec",
    "KOSpec",
    "RMISpec",
    "SYRMISpec",
    "PGMSpec",
    "PGMBicriteriaSpec",
    "RSSpec",
    "BTreeSpec",
    "GappedSpec",
    "InsertReport",
    "NeedsRebuild",
    "updatable_kinds",
]
