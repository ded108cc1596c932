"""tier_device_ms: device milliseconds per request of the tier's
programs (``dist.sharded_index._lookup_vmapped``: route + every shard's
lookup; ``_owner_histogram``: the telemetry route), from the trace.
Moves ops_per_s."""

from benchmarks.chip.readers import program_ms_per_request

PROGRAMS = ("_lookup_vmapped", "_owner_histogram")


def read(ctx):
    return program_ms_per_request(ctx, PROGRAMS)
