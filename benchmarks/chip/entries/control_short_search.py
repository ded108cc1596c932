"""Control, not a cell's entry: the plain reference put in the
program's place with one guarantee broken.

A branch-free binary search over the whole table on the chip, one step
short of ``ceil(log2 n)``: the step a later change would be tempted to
drop.  Its answers are off by one wherever the last step mattered, so a
run through it must come out not correct.  It imports nothing of the
program.  ``benchmarks/chip/control.py`` drives it; the benchmark's own
runs never do."""

from __future__ import annotations

import math
import time
from functools import partial

import numpy as np


def _search(table, q, *, steps: int):
    import jax.numpy as jnp

    n = table.shape[0]
    base = jnp.zeros(q.shape, dtype=jnp.int64)
    length = n
    for _ in range(steps):
        half = length // 2
        base = jnp.where(table[base + half] <= q, base + half, base)
        length -= half
    return base + (table[base] <= q).astype(jnp.int64) - 1


class Entry:
    def __init__(self, cfg: dict, table: np.ndarray):
        import jax

        t0 = time.perf_counter()
        self.table_d = jax.device_put(table)
        self.table_d.block_until_ready()
        self.timings = {"place_s": time.perf_counter() - t0}
        steps = math.ceil(math.log2(len(table))) - 1
        self._fn = jax.jit(partial(_search, steps=steps))
        self.name = f"full-table search, {steps} steps"

    def call(self, q: np.ndarray):
        return self._fn(self.table_d, q)

    def model_device_bytes(self) -> int:
        return 0

    def space_bytes(self) -> int:
        return 0

    def needed_bytes(self, ranks: np.ndarray):
        return None


def build(cfg: dict, table: np.ndarray) -> Entry:
    return Entry(cfg, table)
