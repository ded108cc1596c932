"""Arithmetic shared by the per-layer readers in ``layers/``.

Each reader gets ``ctx``, a dict with

* ``trace``: the reduction of the window's profiler trace
  (:mod:`benchmarks.chip.scopes`), or None; without a device plane it
  holds only the host keys (``window_s``, ``span_s``);
* ``counters``: the window's increase of every ``repro.obs`` counter
  sample (:func:`counter_increase`; read it with :func:`counter`);
* ``requests``: requests completed in the traced window;
* ``timings``: set-up seconds by phase;
* ``needed_bytes_per_request``: from :mod:`benchmarks.chip.needed_bytes`,
  or None;
* ``peaks``: the chip's row of :mod:`benchmarks.chip.peaks`, or None.

A reader that finds nothing to read returns None, and the metric is
left out of the result.  A reader may set ``SPANS``, the program's host
spans it reads (``repro.obs.span`` names): the traced run collects them
with the harness's own, and the reduction's ``span_s``,
``idle_by_span`` and ``idle_gaps`` name them.  ``NEEDS_BYTES = True``
asks for ``needed_bytes_per_request``.
"""

from __future__ import annotations


def trace_value(ctx: dict, key: str):
    """``ctx["trace"][key]``, or None where the reduction has no such key."""
    return (ctx.get("trace") or {}).get(key)


def program_s(ctx: dict, names: tuple) -> float | None:
    """Device seconds of the programs whose name holds one of ``names``."""
    programs = trace_value(ctx, "programs")
    if not programs:
        return None
    s = sum(v for k, v in programs.items() if any(n in k for n in names))
    return s if s > 0 else None


def per_request_ms(ctx: dict, seconds: float | None) -> float | None:
    """``seconds`` of the window as milliseconds a request."""
    if seconds is None or not ctx.get("requests"):
        return None
    return 1e3 * seconds / ctx["requests"]


def program_ms_per_request(ctx: dict, names: tuple) -> float | None:
    return per_request_ms(ctx, program_s(ctx, names))


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


def _labels(labels: dict) -> tuple:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def counter_increase(before: dict, after: dict) -> dict:
    """``{(name, labels): increase}`` of every counter sample between two
    ``repro.obs`` snapshots, ``labels`` a sorted tuple of ``(label,
    value)`` pairs; a sample only in ``after`` counts from 0."""
    from repro import obs

    return {(name, _labels(s["labels"])): s["value"]
            for name, entry in obs.diff(before, after).items() if entry["type"] == "counter"
            for s in entry["samples"]}


def counter(ctx: dict, name: str, **labels) -> float | None:
    """The window's increase of the counter sample ``name`` with exactly
    ``labels``, or None where the program has no such sample."""
    return (ctx.get("counters") or {}).get((name, _labels(labels)))
