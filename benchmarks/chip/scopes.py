#!/usr/bin/env python3
"""Where a traced window's device time goes by the program's named
scopes, and what the host was doing while the chip sat idle, by the
program's own host spans.

Reads the same ``.xplane.pb`` as :mod:`benchmarks.chip.trace`, on the
same clock, for what that reduction does not split:

* ``scopes``: scope (``predict``, ``search``: the ``jax.named_scope``s of
  ``repro.index.index.lookup_impl``) -> device seconds, the union of the
  intervals of that scope's ops inside the window, so an op nested in a
  ``%while`` is not counted twice;
* ``unscoped``: program -> device seconds in which the program's ops ran
  and none of them was under a scope (the u64 table's limb split, the
  route, rank rebasing);
* ``unscoped_ops``: the ``TOP`` longest ops under no scope, custom calls
  named with their target (``%custom-call.16 X64SplitHigh``);
* ``idle_by_span``: innermost host span -> idle seconds, over all idle
  time, averaged over the chips (``"other"`` where the host was in no
  span): it sums to the window's idle time;
* ``idle_gaps``: the ``TOP`` longest idle gaps, each named for the host
  spans it overlaps most, ``<outermost>/<innermost>``
  (``entry_call/tier.telemetry.pull``), or ``"other"``.

The device events carry no ``op_name`` for some ops (a ``%while`` has
none), so an op's scope is read from the compiled HLO module the trace
keeps for each program on its ``/host:metadata`` plane: once per
instruction, not per event.  JAX's persistent compilation cache keys a
program without that metadata, so a run whose executable was compiled
from a source without the scopes (and cached) reads none.  Host spans
are the benchmark's own (``trace.HOST_SPANS``) and the program's
(``PROGRAM_SPANS``, written by ``repro.obs.span``); spans on one thread
nest by time.

    python3 benchmarks/chip/scopes.py <trace.xplane.pb>

prints the reduction as one JSON line.
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

if __name__ == "__main__":
    sys.path[0] = str(Path(__file__).resolve().parents[2])  # the checkout's root

from benchmarks.chip.trace import (  # noqa: E402
    _DEVICE_PLANE,
    _MODULES_LINE,
    _OPS_LINE,
    HOST_SPANS,
    TOP,
    WINDOW_SPAN,
    busy_ns,
    clip,
    idle_gaps,
    op_name,
    program_name,
)

SCOPES = ("predict", "search")
#: the program's own host spans (``dist.sharded_index._record_tier_metrics``)
PROGRAM_SPANS = ("tier.telemetry", "tier.telemetry.pull", "tier.telemetry.record")
OTHER = "other"
_METADATA_PLANE = "/host:metadata"
_HLO_STAT = "Hlo Proto"
# a scope is one path component of op_name, bare or wrapped: "predict", "vmap(search)"
_SCOPE = re.compile(r"(?:^|/)(?:[\w.]+\()*(%s)\)*(?=/|$)" % "|".join(SCOPES))
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def scope_of(op_path: str) -> str | None:
    """``jit(_lookup_vmapped)/vmap(search)/while`` -> ``search``; None
    when no component is a scope."""
    m = _SCOPE.search(op_path)
    return m.group(1) if m else None


def op_label(event_name: str) -> str:
    """An op event's name as the breakdown prints it: ``%custom-call.16
    X64SplitHigh`` for a custom call, ``%while.4`` otherwise."""
    m = _TARGET.search(event_name)
    return f"{op_name(event_name)} {m.group(1)}" if m else op_name(event_name)


# -- protobuf wire format: just enough of XSpace and HloProto -----------------


def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def hlo_op_names(hlo_proto) -> dict:
    """``%<instruction>`` -> ``op_name`` metadata of every instruction of a
    serialized ``HloProto`` (hlo_module=1 > computations=3 >
    instructions=2 > name=1, metadata=7 > op_name=2)."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f, comp in _fields(module):
            if f != 3:
                continue
            for f, ins in _fields(comp):
                if f != 2:
                    continue
                name = path = None
                for g, v in _fields(ins):
                    if g == 1:
                        name = bytes(v).decode()
                    elif g == 7:
                        path = next((bytes(x).decode() for h, x in _fields(v) if h == 2), None)
                if name and path:
                    out["%" + name] = path
    return out


def program_scopes(xspace: bytes) -> dict:
    """Program event name (``jit__lookup_jit(123)``) -> ``{%instruction:
    scope}`` for its instructions under a scope, from the compiled HLO
    modules on the trace's ``/host:metadata`` plane (XSpace planes=1 >
    XPlane name=2, event_metadata=4, stat_metadata=5)."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if next((bytes(v).decode() for g, v in fields if g == 2), None) != _METADATA_PLANE:
            continue
        stat_names, events = {}, []
        for g, entry in fields:
            if g in (4, 5):  # map<int64, ...> entries: key=1, value=2
                value = next(v for h, v in _fields(entry) if h == 2)
                md = dict(_fields(value))
                if g == 5:
                    stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
                else:
                    events.append(value)
        for value in events:
            name, protos = None, []
            for h, v in _fields(value):
                if h == 2:
                    name = bytes(v).decode()
                elif h == 5:  # XStat: metadata_id=1, bytes_value=6
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) == _HLO_STAT and 6 in st:
                        protos.append(st[6])
            for proto in protos:
                scoped = {k: scope_of(p) for k, p in hlo_op_names(proto).items()}
                out[name] = {k: s for k, s in scoped.items() if s}
    return out


def load(path: str) -> tuple:
    """``(chips, spans, window, op_scopes)`` from an ``.xplane.pb`` file:
    as :func:`benchmarks.chip.trace.load`, with the program's spans
    among the host spans and each program's scoped instructions."""
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    chips, spans, window = {}, [], None
    names = (*HOST_SPANS, *PROGRAM_SPANS)
    for plane in ProfileData.from_serialized_xspace(raw).planes:
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
                    elif e.name in names:
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return chips, spans, window, program_scopes(raw)


# -- host spans -----------------------------------------------------------------


def span_pieces(spans) -> list:
    """Cut nested host spans ``(name, start, end)`` into disjoint, sorted
    ``(start, end, path)`` pieces, ``path`` the names of the spans that
    cover the piece, outermost first.  Time in no span has no piece."""
    out, stack, t = [], [], None

    def close_to(x):
        nonlocal t
        while stack and stack[-1][1] <= x:
            end = stack[-1][1]
            if end > t:
                out.append((t, end, tuple(n for n, _ in stack)))
                t = end
            stack.pop()
        if stack and x > t:
            out.append((t, x, tuple(n for n, _ in stack)))
        t = x if t is None else max(t, x)

    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        if e > s:
            close_to(s)
            stack.append((name, e))
    if stack:
        close_to(max(e for _, e in stack))
    return out


def idle_by_span(gaps, pieces) -> dict:
    """Innermost span -> idle ns, in one sweep over gaps and pieces, both
    sorted by start; the part of a gap under no span goes to ``"other"``.
    Gaps may overlap (one list for several chips)."""
    out = defaultdict(float)
    for _, _, path in pieces:
        out[path[-1]] += 0.0
    j = 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        covered, k = 0.0, j
        while k < len(pieces) and pieces[k][0] < ge:
            ov = min(ge, pieces[k][1]) - max(gs, pieces[k][0])
            if ov > 0:
                out[pieces[k][2][-1]] += ov
                covered += ov
            k += 1
        if ge - gs > covered:
            out[OTHER] += (ge - gs) - covered
    return dict(out)


def gap_name(gap, pieces, starts) -> str:
    """The span path a gap overlaps most, ``<outermost>/<innermost>``, or
    ``"other"``; ``starts`` are the pieces' starts (for bisection)."""
    by = defaultdict(float)
    k = max(bisect.bisect_right(starts, gap[0]) - 1, 0)
    while k < len(pieces) and pieces[k][0] < gap[1]:
        s, e, path = pieces[k]
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > 0:
            by[path[0] if len(path) == 1 else f"{path[0]}/{path[-1]}"] += ov
        k += 1
    return max(by, key=by.get) if by else OTHER


# -- the reduction ----------------------------------------------------------------


def reduce(chips: dict, spans: list, window: tuple, op_scopes: dict | None = None) -> dict:
    """``chips``, ``spans`` and ``window`` as :func:`benchmarks.chip.trace.reduce`
    takes them; ``op_scopes``: program event name -> ``{%instruction:
    scope}`` (:func:`program_scopes`).  Returns the keys the module
    docstring lists, in seconds."""
    lo, hi = window
    if not chips or hi <= lo:
        return {}
    op_scopes = op_scopes or {}
    scopes, unscoped, unscoped_ops, gaps = defaultdict(float), defaultdict(float), defaultdict(float), []
    for ev in chips.values():
        mods = sorted((s, e, name) for name, s, e in ev["modules"])
        starts = [m[0] for m in mods]
        by_scope, by_prog, scoped_by_prog = defaultdict(list), defaultdict(list), defaultdict(list)
        for name, s, e in ev["ops"]:
            k = bisect.bisect_right(starts, s) - 1
            prog = mods[k][2] if k >= 0 and s < mods[k][1] else None
            sc = op_scopes.get(prog, {}).get(op_name(name))
            by_prog[prog].append((s, e))
            if sc:
                by_scope[sc].append((s, e))
                scoped_by_prog[prog].append((s, e))
            else:
                for cs, ce in clip([(s, e)], lo, hi):
                    unscoped_ops[op_label(name)] += (ce - cs) * 1e-9
        for sc, iv in by_scope.items():
            scopes[sc] += busy_ns(iv, lo, hi) * 1e-9
        for prog, iv in by_prog.items():
            ns = busy_ns(iv, lo, hi) - busy_ns(scoped_by_prog[prog], lo, hi)
            unscoped[OTHER if prog is None else program_name(prog)] += ns * 1e-9
        gaps += idle_gaps([(s, e) for _, s, e in ev["ops"]], lo, hi)
    gaps.sort()
    names = (*HOST_SPANS, *PROGRAM_SPANS)
    pieces = []
    for s, e, path in span_pieces([sp for sp in spans if sp[0] in names]):
        pieces += [(cs, ce, path) for cs, ce in clip([(s, e)], lo, hi)]
    starts = [p[0] for p in pieces]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "scopes": dict(scopes),
        "unscoped": dict(unscoped),
        "unscoped_ops": sorted(([k, v] for k, v in unscoped_ops.items()), key=lambda x: -x[1])[:TOP],
        "idle_by_span": {k: v * 1e-9 / len(chips) for k, v in idle_by_span(gaps, pieces).items()},
        "idle_gaps": [[gap_name(g, pieces, starts), (g[1] - g[0]) * 1e-9] for g in longest],
    }


def main(argv=None) -> int:
    (path,) = sys.argv[1:] if argv is None else argv
    chips, spans, window, op_scopes = load(path)
    print(json.dumps(reduce(chips, spans, window, op_scopes) if window else {}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
