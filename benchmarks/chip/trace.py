"""The profiler trace of the measured window: where the file is, which
host spans are collected, and the interval arithmetic of the reduction.

The reduction itself is :mod:`benchmarks.chip.scopes` (one reduction for
the per-layer readers and for ``scopes.py``'s command line); :func:`load`
and :func:`reduce` hand over to it.  The collected host spans are the
harness's own (``make_request``, ``entry_call``, ``answer_to_host``,
written by ``loops/<loop>.py``) and those the cell's readers declare in
``SPANS``.  The arithmetic works on ``(start_ns, end_ns)`` intervals so
that it can be checked on a hand-made list.
"""

from __future__ import annotations

import glob
import os
import re

HOST_SPANS = ("make_request", "entry_call", "answer_to_host")
WINDOW_SPAN = "window"
TOP = 10
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


def merged(intervals) -> list:
    """Sorted, disjoint ``(start, end)`` intervals covering the input."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in clip(merged(intervals), lo, hi))


def idle_gaps(intervals, lo: float, hi: float) -> list:
    """The ``(start, end)`` stretches of ``[lo, hi]`` that no interval covers."""
    gaps, t = [], lo
    for s, e in clip(merged(intervals), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def program_name(event_name: str) -> str:
    """``jit__lookup_jit(123)`` -> ``jit__lookup_jit``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def op_name(event_name: str) -> str:
    """An op event's HLO text ``%fusion.1 = u32[8]{0} fusion(...)`` -> ``%fusion.1``."""
    return event_name.split(" = ", 1)[0].strip()


def xplane_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def collected_spans(readers) -> tuple:
    """``HOST_SPANS`` and then every span the reader modules declare in
    ``SPANS``, each once."""
    names = list(HOST_SPANS)
    for mod in readers:
        for n in getattr(mod, "SPANS", ()):
            if n not in names:
                names.append(n)
    return tuple(names)


def load(path: str, names: tuple = HOST_SPANS) -> tuple:
    """``(chips, spans, window, op_scopes)``: :func:`benchmarks.chip.scopes.load`."""
    from benchmarks.chip import scopes

    return scopes.load(path, names)


def reduce(chips: dict, spans: list, window: tuple, op_scopes: dict | None = None,
           names: tuple = HOST_SPANS) -> dict:
    """The reduction's keys: :func:`benchmarks.chip.scopes.reduce`."""
    from benchmarks.chip import scopes

    return scopes.reduce(chips, spans, window, op_scopes, names)
