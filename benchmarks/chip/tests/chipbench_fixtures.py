"""A throwaway benchmark root for the CPU tests: a copy of
``benchmarks/chip`` with small configurations and mixes added as new
files and named in a ``BENCHMARK.json`` of its own, the way a later
change adds a cell.  Each small cell stands in for one real cell; real
cells with no stand-in are left out."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]

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
#: small cell -> (config, mix, the real cell it stands in for)
CELLS = {"tiny-syrmi.tiny-uniform": ("tiny-syrmi", "tiny-uniform", "osm200m-syrmi.sosd-uniform"),
         "tiny-pgmm-tier4.tiny-zipf": ("tiny-pgmm-tier4", "tiny-zipf", "osm200m-pgmm-tier4.ycsb-c")}


def small_root(tmp: Path, source: Path = REPO) -> Path:
    """``tmp`` laid out as a checkout whose manifest names only the
    small cells: ``source``'s benchmark files and metrics, each metric's
    cell list mapped to the stand-ins of the real cells it names.  A
    per-layer metric none of whose cells has a stand-in is left out."""
    root = Path(tmp)
    dst = root / "benchmarks" / "chip"
    shutil.copytree(Path(source) / "benchmarks" / "chip", dst,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, cfg in SMALL_CONFIGS.items():
        (dst / "configs" / f"{name}.json").write_text(json.dumps({"name": name, **cfg}))
    for name, mix in SMALL_MIXES.items():
        (dst / "mixes" / f"{name}.json").write_text(json.dumps(mix))
    man = json.loads((Path(source) / "BENCHMARK.json").read_text())
    stand_in = {real: cell for cell, (_, _, real) in CELLS.items()}
    man["configs"] = [
        {"name": name, "source": "https://arxiv.org/abs/1911.13014",
         "file": f"benchmarks/chip/configs/{name}.json", "reduced": ["keys"], "why": "CPU test"}
        for name in SMALL_CONFIGS
    ]
    man["workloads"] = [
        {"name": cell, "config": c, "traffic": t, "chips": 1, "why": f"CPU stand-in for {real}"}
        for cell, (c, t, real) in CELLS.items()
    ]
    for m in [*man["end_to_end"], *man["per_layer"]]:
        if "workloads" in m:
            m["workloads"] = [stand_in[w] for w in m["workloads"] if w in stand_in]
    man["per_layer"] = [m for m in man["per_layer"] if m.get("workloads", True)]
    (root / "BENCHMARK.json").write_text(json.dumps(man, indent=1))
    return root
