"""device_idle_pct: share of the traced window in which no XLA operation
ran on the chip (1 - busy union / window), in %.  Moves ops_per_s."""

from benchmarks.chip.readers import trace_value


def read(ctx):
    return trace_value(ctx, "idle_pct")
