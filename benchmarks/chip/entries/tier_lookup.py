"""Entry: the updatable serving tier, ``TunedTier(table, n_shards,
spec=...)`` with its default policy, answered through
``TunedTier.lookup(q)``.

On one chip the tier resolves to mode ``ref`` (the vmapped all-shards
sweep) and records its routing telemetry on every call, as it serves."""

from __future__ import annotations

import time

import numpy as np

from benchmarks.chip import needed_bytes
from benchmarks.chip.resident import device_bytes


class Entry:
    def __init__(self, cfg: dict, table: np.ndarray):
        import jax
        from repro.index import registry
        from repro.tune.rebuild import TunedTier

        t0 = time.perf_counter()
        spec = registry.spec_for(cfg["kind"], **cfg["params"])
        self.tier = TunedTier(table, n_shards=int(cfg["n_shards"]), spec=spec)
        jax.block_until_ready(self.tier.sidx)
        self.timings = {"build_s": time.perf_counter() - t0}
        self.name = f"{self.tier.spec.display_name()} x{self.tier.sidx.n_shards}"

    def call(self, q: np.ndarray):
        return self.tier.lookup(q)

    def model_device_bytes(self) -> int:
        s = self.tier.sidx
        return device_bytes([*s.index.arrays.values(), s.fences, s.counts, s.offsets])

    def space_bytes(self) -> int:
        return int(self.tier.sidx.space_bytes())

    def needed_bytes(self, ranks: np.ndarray):
        s = self.tier.sidx
        shards = [s.shard(i).arrays for i in range(s.n_shards)]
        rows = int(s.tables.shape[1])
        return needed_bytes.tier_lookup(s.kind, shards, rows, np.asarray(s.offsets), ranks)


def build(cfg: dict, table: np.ndarray) -> Entry:
    return Entry(cfg, table)
