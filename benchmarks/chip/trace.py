"""Reduce a profiler trace of the measured window to the numbers the
per-layer metrics read.

* device busy: the union of the intervals in which an XLA operation ran
  on a chip, inside the window, averaged over the chips used;
* device time per jitted program, from the program (XLA module) events,
  keyed by the program's name;
* the longest idle gaps, each named for the benchmark's own host span
  (``make_request``, ``entry_call``, ``answer_to_host``) that overlaps it
  most, or ``"other"``.

:func:`load` reads an ``.xplane.pb`` file into plain event lists; the
reduction itself works on ``(name, start_ns, end_ns)`` tuples so that it
can be checked on a hand-made list.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

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


def name_gap(gap, spans) -> str:
    """The host span that overlaps ``gap`` most, or ``"other"``."""
    best, best_ns = "other", 0.0
    for name, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def program_name(event_name: str) -> str:
    """``jit__lookup_jit(123)`` -> ``jit__lookup_jit``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def op_name(event_name: str) -> str:
    """An op event's HLO text ``%fusion.1 = u32[8]{0} fusion(...)`` -> ``%fusion.1``."""
    return event_name.split(" = ", 1)[0].strip()


def reduce(chips: dict, spans: list, window: tuple) -> dict:
    """``chips``: chip id -> ``{"ops": [(name, s, e)], "modules": [(name, s, e)]}``;
    ``spans``: host ``(name, s, e)``; ``window``: ``(start_ns, end_ns)``.

    Returns ``busy_s`` and ``window_s`` (busy averaged over chips),
    ``idle_pct``, ``programs`` (program name -> device seconds, summed
    over chips), ``device_ops`` and ``idle_gaps`` (the ``TOP`` largest
    ``[name, seconds]`` pairs).  Ops nest: a ``%while`` op's time holds
    the ops of its body, so ``device_ops`` may add up past ``busy_s``."""
    lo, hi = window
    if not chips or hi <= lo:
        return {}
    busy, programs, ops, gaps = [], defaultdict(float), defaultdict(float), []
    for ev in chips.values():
        intervals = [(s, e) for _, s, e in ev["ops"]]
        busy.append(busy_ns(intervals, lo, hi))
        for name, s, e in ev["modules"]:
            for cs, ce in clip([(s, e)], lo, hi):
                programs[program_name(name)] += (ce - cs) * 1e-9
        for name, s, e in ev["ops"]:
            for cs, ce in clip([(s, e)], lo, hi):
                ops[op_name(name)] += (ce - cs) * 1e-9
        gaps += idle_gaps(intervals, lo, hi)
    host = [sp for sp in spans if sp[0] in HOST_SPANS]
    # only the longest gaps are named: a window holds as many gaps as ops
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    named = [(name_gap(g, host), (g[1] - g[0]) * 1e-9) for g in longest]
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) / len(busy) * 1e-9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "programs": dict(programs),
        "device_ops": sorted(([k, v] for k, v in ops.items()), key=lambda x: -x[1])[:TOP],
        "idle_gaps": [list(x) for x in named],
    }


def xplane_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> tuple:
    """``(chips, spans, window)`` from an ``.xplane.pb`` file: the device
    planes' op and program events, the host spans, and the interval of
    the host's ``window`` span (all on the profiler's one clock)."""
    from jax.profiler import ProfileData

    chips, spans, window = {}, [], None
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (_OPS_LINE, _MODULES_LINE):
                key = "ops" if line.name == _OPS_LINE else "modules"
                chip = chips.setdefault(int(m.group(1)), {"ops": [], "modules": []})
                chip[key] += [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
            elif not m:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in HOST_SPANS:
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return chips, spans, window
