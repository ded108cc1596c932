"""Spans, stopwatches, and the latency-histogram lookup wrapper.

* :func:`span` — ``with span("name"):`` marks the block as a
  ``jax.profiler.TraceAnnotation``: under an active profiler trace it
  lands on the host plane of the same ``.xplane.pb`` as the device ops,
  on one clock; with no trace active it is a C++ no-op.  Records nothing
  into the registry.
* :func:`stopwatch` — the sanctioned way to take a wall-clock delta in
  ``src/repro/`` (analyzer rule R8 flags raw ``time.perf_counter()``
  subtraction outside ``repro.obs``): ``sw = stopwatch(); ...;
  sw.elapsed`` seconds.
* :func:`timed_lookup` — wraps any ``.lookup(...)`` target (``Index``,
  ``ShardedIndex`` via ``sharded_lookup`` partial, ``TunedTier``) and
  records BOTH the host dispatch time and the device completion time
  (``jax.block_until_ready``) into the ``lookup_latency_us`` histogram,
  labeled (kind, backend, tier, phase) — through ONE jitted histogram
  update, so telemetry-on costs at most one extra dispatch per call.
"""

from __future__ import annotations

import time

from . import registry as _registry

__all__ = ["Stopwatch", "span", "stopwatch", "timed_lookup"]


class Stopwatch:
    """Monotonic wall-clock delta without raw ``perf_counter`` math."""

    __slots__ = ("_t0",)

    def __init__(self):
        self._t0 = time.perf_counter()

    def restart(self) -> None:
        self._t0 = time.perf_counter()

    @property
    def elapsed(self) -> float:
        """Seconds since construction / the last :meth:`restart`."""
        return time.perf_counter() - self._t0

    def __enter__(self) -> "Stopwatch":
        self.restart()
        return self

    def __exit__(self, *exc) -> None:
        pass


def stopwatch() -> Stopwatch:
    return Stopwatch()


def span(name: str):
    """A host span named ``name`` in the profiler's trace.

    ``with span("tier.telemetry"): ...`` enters
    ``jax.profiler.TraceAnnotation(name)``.  Spans nest by time on their
    thread: the parent of a span is the span that encloses it.  Nothing
    is recorded when no profiler trace is active, and the span never
    starts one.
    """
    from jax import profiler

    return profiler.TraceAnnotation(name)


def _target_kind(target) -> str:
    kind = getattr(target, "kind", None)
    if kind is None:
        kind = getattr(getattr(target, "spec", None), "kind", "?")
    return str(kind)


def _target_backend(target, kw: dict) -> str:
    be = kw.get("backend")
    if be is None:
        be = getattr(getattr(target, "policy", None), "backend", None)
    return str(be or "xla")


def timed_lookup(target, *args, tier: str = "-", registry=None, **kw):
    """``target.lookup(*args, **kw)`` + latency histograms.

    Records two phases into ``lookup_latency_us``:

    * ``phase=host`` — wall time until the (async) dispatch returns;
    * ``phase=device`` — wall time until ``jax.block_until_ready``,
      i.e. the latency a synchronous caller actually observes.

    Both land through one :meth:`Histogram.observe_groups` call — ONE
    extra jitted dispatch per lookup, zero extra *lookup* traces (the
    histogram update has its own ``obs:hist/update`` trace entry).
    """
    import jax

    labels = dict(
        kind=_target_kind(target), backend=_target_backend(target, kw), tier=str(tier)
    )
    sw = Stopwatch()
    out = target.lookup(*args, **kw)
    host_us = sw.elapsed * 1e6
    jax.block_until_ready(out)
    device_us = sw.elapsed * 1e6
    reg = registry or _registry.default_registry()
    reg.metric("lookup_latency_us").observe_groups(
        [
            ({**labels, "phase": "host"}, [host_us]),
            ({**labels, "phase": "device"}, [device_us]),
        ]
    )
    return out
