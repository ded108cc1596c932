"""A throwaway benchmark root for the CPU tests: a copy of
``benchmarks/chip`` with small configurations and mixes added as new
files and named in a ``BENCHMARK.json`` of its own, the way a later
change adds a cell."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
BENCH = REPO / "benchmarks" / "chip"

SMALL_CONFIGS = {
    "tiny-syrmi": {"dataset": "osm", "keys": 40_000, "kind": "SY-RMI",
                   "params": {"space_pct": 0.05}, "entry": "index_lookup", "backend": "xla"},
    "tiny-pgmm-tier4": {"dataset": "osm", "keys": 40_000, "kind": "PGM_M",
                        "params": {"space_pct": 0.05}, "n_shards": 4, "entry": "tier_lookup"},
}
SMALL_MIXES = {
    "tiny-uniform": {"loop": "ahead", "callers": 1, "depth": 8, "batch": 512, "pool_batches": 4,
                     "keys": {"draw": "uniform_present"}},
    "tiny-zipf": {"loop": "closed", "callers": 1, "batch": 256, "pool_batches": 4,
                  "keys": {"draw": "scrambled_zipfian", "theta": 0.99,
                           "item_space": 10_000_000_000, "zetan": 26.46902820178302}},
}
CELLS = {"tiny-syrmi.tiny-uniform": ("tiny-syrmi", "tiny-uniform"),
         "tiny-pgmm-tier4.tiny-zipf": ("tiny-pgmm-tier4", "tiny-zipf")}


def small_root(tmp: Path) -> Path:
    """``tmp`` laid out as a checkout whose manifest names only the
    small cells; the real manifest's metrics are kept, their cell lists
    renamed to the small cells."""
    root = Path(tmp)
    dst = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, cfg in SMALL_CONFIGS.items():
        (dst / "configs" / f"{name}.json").write_text(json.dumps({"name": name, **cfg}))
    for name, mix in SMALL_MIXES.items():
        (dst / "mixes" / f"{name}.json").write_text(json.dumps(mix))
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    rename = dict(zip([w["name"] for w in man["workloads"]], CELLS))
    man["configs"] = [
        {"name": name, "source": "https://arxiv.org/abs/1911.13014",
         "file": f"benchmarks/chip/configs/{name}.json", "reduced": ["keys"], "why": "CPU test"}
        for name in SMALL_CONFIGS
    ]
    man["workloads"] = [
        {"name": cell, "config": c, "traffic": t, "chips": 1, "why": "CPU test"}
        for cell, (c, t) in CELLS.items()
    ]
    for m in [*man["end_to_end"], *man["per_layer"]]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(man, indent=1))
    return root
