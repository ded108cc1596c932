"""tier_roofline_pct: least time / tier_device_ms, in %.  The counted
work is the fence route plus ONE owner shard's lookup per query
(benchmarks/chip/needed_bytes.py) over the chip's HBM bandwidth, so
work spent on shards that do not own the query shows as a lower share.
Moves ops_per_s."""

from benchmarks.chip.readers import hbm_roofline_pct

NEEDS_BYTES = True  # reads ``needed_bytes_per_request``
PROGRAMS = ("_lookup_vmapped", "_owner_histogram")


def read(ctx):
    return hbm_roofline_pct(ctx, PROGRAMS)
