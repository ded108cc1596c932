"""lookup_roofline_pct: least time / lookup_device_ms, in %.  The least
time is the HBM bytes the lookup needs (benchmarks/chip/needed_bytes.py)
over the chip's HBM bandwidth; bandwidth is the bound that binds for
dependent 8-byte gathers.  Moves ops_per_s."""

from benchmarks.chip.readers import hbm_roofline_pct

NEEDS_BYTES = True  # reads ``needed_bytes_per_request``
PROGRAMS = ("_lookup_jit",)


def read(ctx):
    return hbm_roofline_pct(ctx, PROGRAMS)
