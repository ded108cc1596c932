"""Loop ``closed``: one caller that sends its next request when the
answer to the last is on the host, reads only.  A request's latency runs
from the call with a host batch to the answer on the host.

The mix may state ``callers`` and ``read_share``; this loop implements
one caller and reads only, and refuses any other value rather than run
something else than the mix says."""

from __future__ import annotations

import time

import numpy as np

IMPLEMENTS = {"callers": 1, "read_share": 1.0}


def problems(params: dict) -> list:
    return [
        f"closed loop: {k} = {v!r} is not implemented (it runs {IMPLEMENTS[k]!r})"
        if k in IMPLEMENTS else f"closed loop takes no parameter {k!r}"
        for k, v in params.items()
        if IMPLEMENTS.get(k, object()) != v
    ]


def drive(entry, batches: list, seconds: float, sample, params: dict) -> tuple:
    """Requests cycle through ``batches`` until ``seconds`` have passed;
    every answer is offered to ``sample``; ``params`` are the mix's
    (checked by ``problems``).  Returns the per-request
    latencies (s) and the window's length (s)."""
    from jax.profiler import TraceAnnotation

    lat = []
    i = 0
    with TraceAnnotation("window"):
        t0 = time.perf_counter()
        while True:
            with TraceAnnotation("make_request"):
                k = i % len(batches)
                q = batches[k]
            t = time.perf_counter()
            with TraceAnnotation("entry_call"):
                out = entry.call(q)
            with TraceAnnotation("answer_to_host"):
                ans = np.asarray(out)
            done = time.perf_counter()
            lat.append(done - t)
            sample.offer(k, ans)
            i += 1
            if done - t0 >= seconds:
                break
    return np.asarray(lat), done - t0
