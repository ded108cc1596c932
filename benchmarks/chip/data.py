"""Key tables made from a seed: the benchmark's own copy of the program's
``osm`` generator, so that no change to the program can change the data.

``osm`` is a synthetic stand-in shaped after SOSD's
``osm_cellids_200M_uint64`` (Kipf et al., arXiv:1911.13014): clustered
cell ids, 2,000 keys a cluster on average, each an exponential offset
(mean 2^34) from a uniform centre.  It is not the dataset: no statistic
of the real file is matched.  ``table(name, n, seed)`` returns the same
sorted, deduplicated uint64 table for the same arguments in every
process (the dataset offset is a crc32, never the salted ``hash``).
"""

from __future__ import annotations

import zlib

import numpy as np

DATASETS = ("osm",)


def _osm(rng: np.random.Generator, n: int) -> np.ndarray:
    n_clusters = max(8, n // 2000)
    centers = rng.integers(0, 2**62, size=n_clusters, dtype=np.uint64)
    assign = rng.integers(0, n_clusters, size=int(n * 1.25))
    spread = rng.exponential(2.0**34, size=int(n * 1.25)).astype(np.uint64)
    return centers[assign] + spread


def table(name: str, n: int, seed: int) -> np.ndarray:
    """Sorted deduplicated uint64 table of exactly ``n`` keys."""
    if name not in DATASETS:
        raise ValueError(f"unknown dataset {name!r}; choose from {DATASETS}")
    rng = np.random.default_rng(seed + zlib.crc32(name.encode()) % (2**31))
    keys = _osm(rng, n)
    out = np.unique(keys.astype(np.uint64))
    if len(out) < n:  # top up (rare): a second draw under another seed
        out = np.unique(np.concatenate([out, table(name, n, seed + 977)]))
    return out[:n]
