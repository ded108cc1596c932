"""R8 — raw wall-clock deltas in ``src/repro/`` outside ``repro.obs``.

PR 8 unified telemetry behind ``repro.obs``: latency measured with ad-hoc
``time.perf_counter()`` subtraction bypasses the registry — it reaches no
histogram, no snapshot, no SLO gate, and silently diverges from the
distributions the bench-trend baselines assert on.  Library code takes
wall-clock deltas through ``repro.obs.timing`` instead: ``stopwatch()``
for build-time accounting, ``timed_lookup`` for lookup latency
(``span("name")`` marks a block in a profiler trace and measures nothing).

Scope: ``src/repro/`` only, minus ``src/repro/obs/`` (the one place the
raw clock is allowed — it *implements* the stopwatch).  ``benchmarks/``
and ``tools/`` are exempt: harness plumbing (best-of-reps loops, CI
timers) is not serving telemetry.

A timer *call* alone does not flag — only a call whose value flows into
a subtraction (directly, or through a name assigned in the same scope):
that is the "record a delta" signature.
"""

from __future__ import annotations

import ast
from pathlib import Path

from .framework import AstRule, Module

#: the timer functions whose deltas belong in repro.obs.timing
_TIMER_ATTRS = frozenset({"perf_counter", "perf_counter_ns", "time", "monotonic", "monotonic_ns"})
_HINT = (
    "take deltas through repro.obs.timing — stopwatch().elapsed for build "
    "accounting, timed_lookup() for serving latency — so they land "
    "in the registry histograms"
)


def _in_scope(rel: str) -> bool:
    if "analysis_fixtures" in rel:
        return Path(rel).name.startswith("r8")
    return rel.startswith("src/repro/") and not rel.startswith("src/repro/obs/")


def _enclosing_scope(node: ast.AST) -> ast.AST:
    while node is not None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.Module)):
            return node
        node = getattr(node, "_parent", None)
    return node


class RawTimingRule(AstRule):
    id = "R8"
    title = "raw timing outside repro.obs"
    blurb = (
        "`time.perf_counter()`/`time.time()` deltas taken in `src/repro/` "
        "outside the repro.obs layer — latency that bypasses the unified "
        "registry histograms (benchmarks/ and tools/ are exempt)"
    )

    def check_module(self, mod: Module):
        if not _in_scope(mod.rel):
            return
        timer_aliases = self._timer_aliases(mod.tree)
        # names assigned from a timer call, per enclosing scope
        assigned: dict = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign) and self._is_timer_call(node.value, timer_aliases):
                scope = _enclosing_scope(node)
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        assigned.setdefault(scope, set()).add(t.id)
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub)):
                continue
            scope_names = assigned.get(_enclosing_scope(node), set())
            for side in (node.left, node.right):
                if self._is_timer_call(side, timer_aliases) or (
                    isinstance(side, ast.Name) and side.id in scope_names
                ):
                    yield mod.finding(
                        self.id,
                        node,
                        "raw wall-clock delta recorded outside repro.obs",
                        hint=_HINT,
                    )
                    break

    @staticmethod
    def _timer_aliases(tree: ast.AST) -> frozenset:
        """Local names bound to timer functions via ``from time import ...``."""
        names = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module == "time":
                for alias in node.names:
                    if alias.name in _TIMER_ATTRS:
                        names.add(alias.asname or alias.name)
        return frozenset(names)

    @staticmethod
    def _is_timer_call(node: ast.AST, aliases: frozenset) -> bool:
        if not isinstance(node, ast.Call):
            return False
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in _TIMER_ATTRS:
            return isinstance(fn.value, ast.Name) and fn.value.id == "time"
        return isinstance(fn, ast.Name) and fn.id in aliases
