"""device_idle_pct: share of the traced window in which no XLA operation
ran on the chip (1 - busy union / window), in %.  Moves ops_per_s."""


def read(ctx):
    tr = ctx.get("trace")
    return tr["idle_pct"] if tr else None
