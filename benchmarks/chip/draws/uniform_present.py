"""Key draw ``uniform_present``: SOSD's lookups, every query a key of the
table drawn uniformly.  It takes no parameters."""

from __future__ import annotations

import numpy as np


def problems(params: dict) -> list:
    return [f"uniform_present takes no parameter {k!r}" for k in params]


def positions(rng: np.random.Generator, params: dict, n: int, size: int) -> np.ndarray:
    """Row positions in a table of ``n`` keys for ``size`` queries."""
    return rng.integers(0, n, size=size, dtype=np.int64)
