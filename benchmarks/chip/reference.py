"""The plain reference and the comparison that decides ``correct``.

Every configuration here states one guarantee: each query gets its exact
predecessor rank, the largest ``i`` with ``table[i] <= q`` (``-1`` when
``q`` is below every key).  The reference computes that with
``np.searchsorted`` on the benchmark's own host copy of the table; it
imports nothing of the program and reads nothing the program built.
"""

from __future__ import annotations

import numpy as np


def predecessor_rank(table: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """Exact predecessor ranks (int64); sorted probes keep the search's
    reads local, which is several times faster at 200M keys."""
    order = np.argsort(queries, kind="stable")
    out = np.empty(len(queries), dtype=np.int64)
    out[order] = np.searchsorted(table, queries[order], side="right")
    return out - 1


def wrong_answers(got, want: np.ndarray) -> int:
    """Positions where ``got`` and ``want`` disagree: a differing rank,
    plus every answer missing from (or extra in) a misshapen ``got``."""
    got = np.asarray(got).reshape(-1)
    m = min(len(got), len(want))
    return int(np.count_nonzero(got[:m] != want[:m])) + abs(len(want) - len(got))
