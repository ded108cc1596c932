"""Key draw ``scrambled_zipfian``: YCSB's ScrambledZipfianGenerator over
the table's keys.  Zipfian ranks over ``item_space`` items at constant
``theta`` (with ``zetan``, the generalised harmonic number of
``item_space`` at ``theta``, as YCSB precomputes it) are hashed with
FNV-1a and taken modulo the table's length, so the popular keys are
spread over the table."""

from __future__ import annotations

import numpy as np

PARAMS = ("theta", "item_space", "zetan")

_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)


def problems(params: dict) -> list:
    out = [f"scrambled_zipfian takes no parameter {k!r}" for k in params if k not in PARAMS]
    out += [f"scrambled_zipfian needs {k!r}" for k in PARAMS if k not in params]
    if not out and not 0.0 < float(params["theta"]) < 1.0:
        out.append("scrambled_zipfian: theta must lie in (0, 1)")
    return out


def fnv1a64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` over the eight bytes of each value,
    least significant first, with its final ``Math.abs``."""
    v = np.asarray(values, dtype=np.uint64).copy()
    h = np.full(v.shape, _FNV_OFFSET, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            v >>= np.uint64(8)
            h *= _FNV_PRIME
    return np.abs(h.view(np.int64)).view(np.uint64)


def zipfian_ranks(rng: np.random.Generator, size: int, theta: float, items: int, zetan: float):
    """YCSB's ``ZipfianGenerator`` (Gray et al., SIGMOD 1994): ranks in
    ``[0, items)``, rank 0 the most popular, P(rank r) ~ 1/(r+1)^theta."""
    zeta2 = 1.0 + 0.5**theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(size)
    uz = u * zetan
    r = np.floor(items * np.power(eta * u - eta + 1.0, alpha))
    r = np.minimum(r, items - 1).astype(np.uint64)
    r[uz < zeta2] = 1
    r[uz < 1.0] = 0
    return r


def positions(rng: np.random.Generator, params: dict, n: int, size: int) -> np.ndarray:
    """Row positions in a table of ``n`` keys for ``size`` queries."""
    r = zipfian_ranks(rng, size, float(params["theta"]), int(params["item_space"]),
                      float(params["zetan"]))
    return (fnv1a64(r) % np.uint64(n)).astype(np.int64)
