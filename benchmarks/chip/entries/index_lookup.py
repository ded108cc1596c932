"""Entry: one learned index over a table placed on the chip once,
answered through ``Index.lookup(table_d, q, backend=...)``.

The configuration names the kind, its build parameters and the backend;
the index is built through ``repro.index.build``, the program's normal
entry point."""

from __future__ import annotations

import time

import numpy as np

from benchmarks.chip import needed_bytes
from benchmarks.chip.resident import device_bytes


class Entry:
    def __init__(self, cfg: dict, table: np.ndarray):
        import jax
        from repro import index as ix

        t0 = time.perf_counter()
        self.index = ix.build(cfg["kind"], table, **cfg["params"])
        jax.block_until_ready(self.index)
        t1 = time.perf_counter()
        self.table_d = jax.device_put(table)
        self.table_d.block_until_ready()
        self.timings = {"build_s": t1 - t0, "place_s": time.perf_counter() - t1}
        self.backend = cfg["backend"]
        self.rows = len(table)
        self.name = self.index.name

    def call(self, q: np.ndarray):
        return self.index.lookup(self.table_d, q, backend=self.backend)

    def model_device_bytes(self) -> int:
        return device_bytes(self.index.arrays.values())

    def space_bytes(self) -> int:
        return int(self.index.space_bytes())

    def needed_bytes(self, ranks: np.ndarray):
        return needed_bytes.index_lookup(self.index.kind, self.index.arrays, self.rows, ranks)


def build(cfg: dict, table: np.ndarray) -> Entry:
    return Entry(cfg, table)
