"""repro.obs — unified metrics / tracing / profiling (docs/observability.md).

One labeled registry (:mod:`repro.obs.registry`) backs every telemetry
surface in the project; :mod:`repro.obs.timing` adds spans, stopwatches
and the device-latency ``timed_lookup`` wrapper; ``python -m repro.obs``
dumps/diffs JSONL snapshot exports.

Import discipline: this package imports nothing from ``repro.*`` at
module scope (the jitted histogram update and the trace-count collector
bind lazily), so any layer may depend on it — and the telemetry-off
lookup paths never import it at call time.
"""

from __future__ import annotations

import sys

from .registry import (
    CATALOGUE,
    Counter,
    Gauge,
    Histogram,
    Registry,
    default_registry,
    diff,
    exp_edges,
    find_sample,
    from_jsonl,
    hist_quantile,
    metric,
    metric_catalogue,
    register_collector,
    reset,
    sample_value,
    snapshot,
    to_jsonl,
)
from .timing import Stopwatch, span, stopwatch, timed_lookup

__all__ = [
    "CATALOGUE",
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "Stopwatch",
    "default_registry",
    "diff",
    "exp_edges",
    "find_sample",
    "from_jsonl",
    "hist_quantile",
    "metric",
    "metric_catalogue",
    "register_collector",
    "reset",
    "sample_value",
    "snapshot",
    "span",
    "stopwatch",
    "timed_lookup",
    "to_jsonl",
]


def _collect_index_traces(reg: Registry) -> None:
    """Mirror ``repro.index.trace_counts()`` into ``index_traces`` gauges,
    and ``repro.index.u64_table_traces()`` into the
    ``lookup_u64_table_traces`` counter, at snapshot time.  Polls
    ``sys.modules`` only — never forces the index machinery in just to
    report that it was never used."""
    ix = sys.modules.get("repro.index")
    if ix is None:
        return
    g = reg.metric("index_traces")
    g.clear()  # trace counts can reset (reset_trace_counts); gauges follow
    for (kind, backend), n in ix.trace_counts().items():
        g.set(float(n), kind=kind, backend=backend)
    c = reg.metric("lookup_u64_table_traces")
    for program, n in ix.u64_table_traces().items():
        c.set_value(float(n), program=program)


register_collector(_collect_index_traces)
