"""Arithmetic shared by the per-layer readers in ``layers/``.

Each reader gets ``ctx``, a dict with ``trace`` (the reduction of
:mod:`benchmarks.chip.trace`, or None), ``requests`` (requests completed
in the traced window), ``timings`` (set-up seconds by phase),
``needed_bytes_per_request`` (from :mod:`benchmarks.chip.needed_bytes`,
or None) and ``peaks`` (the chip's row of :mod:`benchmarks.chip.peaks`).
A reader that finds nothing to read returns None, and the metric is
left out of the result.
"""

from __future__ import annotations


def program_s(ctx: dict, names: tuple) -> float | None:
    """Device seconds of the programs whose name holds one of ``names``."""
    tr = ctx.get("trace")
    if not tr:
        return None
    s = sum(v for k, v in tr["programs"].items() if any(n in k for n in names))
    return s if s > 0 else None


def program_ms_per_request(ctx: dict, names: tuple) -> float | None:
    s = program_s(ctx, names)
    if s is None or not ctx.get("requests"):
        return None
    return 1e3 * s / ctx["requests"]


def hbm_roofline_pct(ctx: dict, names: tuple) -> float | None:
    """Least time over device time, in %: the least time is the HBM bytes
    a request needs over the chip's HBM bandwidth (the bound that binds
    for dependent 8-byte gathers)."""
    ms = program_ms_per_request(ctx, names)
    need = ctx.get("needed_bytes_per_request")
    if ms is None or not need:
        return None
    least_s = need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms * 1e-3)
