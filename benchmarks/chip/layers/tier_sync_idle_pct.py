"""tier_sync_idle_pct: share of the traced window in which the chip was
idle while the host was inside the tier's telemetry sync
(``dist.sharded_index._record_tier_metrics``: the ``tier.telemetry``
span and its ``.pull`` and ``.record`` children, innermost span), in %.
None where the trace holds none of those spans.  Moves ops_per_s."""

from benchmarks.chip.readers import trace_value

SPANS = ("tier.telemetry", "tier.telemetry.pull", "tier.telemetry.record")


def read(ctx):
    by_span = trace_value(ctx, "idle_by_span") or {}
    idle = [by_span[n] for n in SPANS if n in by_span]
    if not idle:
        return None
    return 100.0 * sum(idle) / trace_value(ctx, "window_s")
