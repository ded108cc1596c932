"""Loop ``ahead``: one caller that keeps ``depth`` requests in flight,
reads only.  It sends a request, and while fewer than ``depth`` are
unanswered it sends the next without waiting; it reads the answers in
the order it sent them.  This is SOSD's bulk lookup workload: what the
user gets is answers a second, and the chip stays fed while the host
stands still for as long as ``depth`` requests take on the chip.

When ``seconds`` are up it sends nothing more, waits for every request
it sent, and reads the clock after that wait: the window's work is all
that was sent, over all of that time.  A request's latency runs from its
call with a host batch to its answer on the host, queueing behind the
requests sent before it included.

The mix states ``depth``, and may state ``callers`` and ``read_share``;
this loop implements one caller and reads only, and refuses any other
value rather than run something else than the mix says."""

from __future__ import annotations

import time
from collections import deque

import numpy as np

IMPLEMENTS = {"callers": 1, "read_share": 1.0}


def problems(params: dict) -> list:
    out = []
    depth = params.get("depth")
    if not isinstance(depth, int) or isinstance(depth, bool) or depth < 1:
        out.append(f"ahead loop: depth must be a whole number of at least 1, not {depth!r}")
    for k, v in params.items():
        if k == "depth":
            continue
        if k not in IMPLEMENTS:
            out.append(f"ahead loop takes no parameter {k!r}")
        elif IMPLEMENTS[k] != v:
            out.append(f"ahead loop: {k} = {v!r} is not implemented (it runs {IMPLEMENTS[k]!r})")
    return out


def drive(entry, batches: list, seconds: float, sample, params: dict) -> tuple:
    """Requests cycle through ``batches`` with up to ``params["depth"]``
    unanswered, sent until ``seconds`` have passed; every answer is
    offered to ``sample``.  Returns the per-request latencies (s) and the
    window's length (s), up to the last answer on the host."""
    from jax.profiler import TraceAnnotation

    depth = int(params["depth"])
    lat = []
    pending = deque()
    i = 0
    with TraceAnnotation("window"):
        t0 = time.perf_counter()
        while True:
            while len(pending) < depth and time.perf_counter() - t0 < seconds:
                with TraceAnnotation("make_request"):
                    k = i % len(batches)
                    q = batches[k]
                t = time.perf_counter()
                with TraceAnnotation("entry_call"):
                    pending.append((k, t, entry.call(q)))
                i += 1
            if not pending:
                break
            k, t, out = pending.popleft()
            with TraceAnnotation("answer_to_host"):
                ans = np.asarray(out)
            lat.append(time.perf_counter() - t)
            sample.offer(k, ans)
        done = time.perf_counter()
    return np.asarray(lat), done - t0
