"""Sharded-tier lookup throughput: shard count x kind x backend.

Measures :func:`repro.dist.sharded_lookup` end-to-end (fence route +
capacity-factored all_to_all exchange + local answer + return) against
the single-table ``Index.lookup`` baseline on the concatenated table,
and emits a JSON report with per-configuration throughput plus the
shared-lookup trace counts.

Run on a forced multi-device CPU platform to exercise the collective
paths::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m benchmarks.sharded_lookup --json out.json

``--trace-budget N`` turns the report into a CI gate: the process exits
non-zero when the total number of shared-lookup traces exceeds N
(compile-count regression gate — the whole point of the pytree Index is
that tiers and sweeps do NOT retrace per model).
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np
import jax
import jax.numpy as jnp

from repro import index as ix
from repro.core.cdf import true_ranks
from repro.dist.sharded_index import ShardedIndex, sharded_lookup
from repro.dist.sharding import ShardingCtx, make_mesh

from .common import time_fn

DEFAULT_KINDS = ("RMI", "PGM", "BTREE")
PARAMS = {
    "L": {},
    "Q": {},
    "C": {},
    "KO": {"k": 7},
    "RMI": {"b": 64},
    "SY-RMI": {"space_pct": 2.0, "ub": 0.04},
    "PGM": {"eps": 32},
    "PGM_M": {"space_pct": 2.0, "a": 1.0},
    "RS": {"eps": 16, "r_bits": 8},
    "BTREE": {"fanout": 8},
}


def _mesh_ctx(n_shards: int):
    if n_shards > 1 and len(jax.devices()) >= n_shards:
        mesh = make_mesh((1, n_shards), ("data", "model"))
        return ShardingCtx(mesh=mesh)
    return None


def run(
    n: int = 1 << 14,
    n_queries: int = 1 << 12,
    shard_counts=(1, 2, 4),
    kinds=DEFAULT_KINDS,
    backends=("xla", "bbs", "pallas"),
):
    from repro.core import as_table

    rng = np.random.default_rng(7)
    table = as_table(rng.integers(0, 2**63, size=n, dtype=np.uint64))
    qs = rng.choice(table, size=n_queries).astype(np.uint64)
    want = true_ranks(table, qs)
    tj, qj = jnp.asarray(table), jnp.asarray(qs)

    ix.reset_trace_counts()
    results = []
    for kind in kinds:
        ref_idx = ix.build(kind, table, **PARAMS[kind])
        for backend in backends:
            dt = time_fn(lambda: ref_idx.lookup(tj, qj, backend=backend))
            results.append(
                {
                    "kind": kind,
                    "backend": backend,
                    "mode": "single",
                    "n_shards": 1,
                    "us_per_query": dt / n_queries * 1e6,
                    "qps": n_queries / dt,
                }
            )
        for n_shards in shard_counts:
            sidx = ShardedIndex.build(kind, table, n_shards=n_shards, **PARAMS[kind])
            ctx = _mesh_ctx(n_shards)
            mode = "a2a" if ctx is not None else "ref"
            for backend in backends:
                fn = lambda: sharded_lookup(  # noqa: E731 — timed thunk
                    sidx, qj, ctx, mode=mode, backend=backend, cap_factor=float(n_shards)
                )
                got = np.asarray(fn())
                if not np.array_equal(got, want):
                    raise AssertionError(
                        f"sharded lookup diverged from reference: {kind}/{n_shards}/{backend}",
                    )
                dt = time_fn(fn)
                results.append(
                    {
                        "kind": kind,
                        "backend": backend,
                        "mode": mode,
                        "n_shards": n_shards,
                        "us_per_query": dt / n_queries * 1e6,
                        "qps": n_queries / dt,
                    }
                )
                print(
                    f"sharded_lookup/{kind}/{backend}/{mode}x{n_shards},"
                    f"{results[-1]['us_per_query']:.6g}us"
                )
    traces = {f"{k}/{b}": v for (k, b), v in sorted(ix.trace_counts().items())}
    return {
        "n": int(n),
        "n_queries": int(n_queries),
        "devices": len(jax.devices()),
        "backend_platform": jax.default_backend(),
        "results": results,
        "trace_counts": traces,
        "total_traces": sum(traces.values()),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=1 << 14, help="table size")
    ap.add_argument("--queries", type=int, default=1 << 12, help="query batch")
    ap.add_argument("--shards", default="1,2,4", help="comma-separated shard counts")
    ap.add_argument("--kinds", default=",".join(DEFAULT_KINDS))
    ap.add_argument("--backends", default="xla,bbs,pallas")
    ap.add_argument("--json", default=None, help="write the JSON report here")
    ap.add_argument(
        "--trace-budget",
        type=int,
        default=None,
        help="fail (exit 1) if total shared-lookup traces exceed this",
    )
    args = ap.parse_args()
    report = run(
        n=args.n,
        n_queries=args.queries,
        shard_counts=tuple(int(s) for s in args.shards.split(",") if s),
        kinds=tuple(k for k in args.kinds.split(",") if k),
        backends=tuple(b for b in args.backends.split(",") if b),
    )
    out = json.dumps(report, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(out + "\n")
    print(out)
    if args.trace_budget is not None and report["total_traces"] > args.trace_budget:
        print(
            f"TRACE BUDGET EXCEEDED: {report['total_traces']} > {args.trace_budget}",
            file=sys.stderr,
        )
        sys.exit(1)


if __name__ == "__main__":
    main()
