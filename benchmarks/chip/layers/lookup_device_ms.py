"""lookup_device_ms: device milliseconds per request of the shared
lookup program (``repro.index._lookup_jit``: model predict + bounded
search), from the trace.  Moves ops_per_s."""

from benchmarks.chip.readers import program_ms_per_request

PROGRAMS = ("_lookup_jit",)


def read(ctx):
    return program_ms_per_request(ctx, PROGRAMS)
