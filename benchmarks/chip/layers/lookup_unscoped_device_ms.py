"""lookup_unscoped_device_ms: device milliseconds per request of the
lookup programs that ``lookup_device_ms`` and ``tier_device_ms`` read,
in which none of their ops ran under a scope: a u64 table's limb split,
the tier's route and rank rebasing.  None where the trace has no scope
at all (every op would read as unscoped).  Moves ops_per_s."""

from benchmarks.chip.layers import lookup_device_ms, tier_device_ms
from benchmarks.chip.readers import per_request_ms, trace_value

PROGRAMS = lookup_device_ms.PROGRAMS + tier_device_ms.PROGRAMS


def read(ctx):
    unscoped = trace_value(ctx, "unscoped")
    if not trace_value(ctx, "scopes") or unscoped is None:
        return None
    return per_request_ms(ctx, sum(v for k, v in unscoped.items()
                                   if any(n in k for n in PROGRAMS)))
