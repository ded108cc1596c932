#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the TPU chips the cell
asks for (it exits non-zero, printing no result, before it builds
anything on any other device).  In one process it:

1. makes the cell's key table from ``--seed`` (``data.py``);
2. builds the program through the cell's entry (``entries/<entry>.py``)
   and places it on the chip;
3. makes the mix's pool of query batches from the seed (``traffic.py``
   with the mix's key draw, ``draws/<draw>.py``);
4. warms the one batch shape up: set-up ends at the first timed request;
5. drives the window for ``--seconds`` by the mix's loop
   (``loops/<loop>.py``), counting any program lowered inside it;
6. after the window, compares a seed-drawn sample of the window's
   answers with the reference (``reference.py``);
7. prints earlier JSON lines (``phase``), then one result line: the
   end-to-end metrics with ``--trace 0``, the per-layer metrics (read by
   ``layers/<metric>.py`` from a profiler trace of the window and the
   program's ``repro.obs`` counters over it) with ``--trace 1``.  The
   numbers compared with their limits go last, in the result's
   ``checks`` and on standard error.

A traced run compiles without the persistent compilation cache: the
cache keys a program without its op metadata, and a program cached from
another source would carry no scopes into the trace.  Its set-up time is
no metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up counts from here: before the heavy imports

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    # the checkout's root, not this directory (its trace.py is no stdlib trace)
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

WARMUP_CALLS = 3
#: answers kept for the check: a reservoir of about this many bytes
SAMPLE_BYTES = 1 << 29
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def emit(out, phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), file=out, flush=True)


def require_chips(devices, chips: int) -> None:
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(
            f"chip benchmark: needs {chips} TPU chip(s), JAX found {len(devices)} "
            f"{devices[0].platform!r} device(s); nothing was built"
        )


def compile_cache(root: Path, use: bool = True) -> str | None:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache`` (a fixed path: the path is
    part of the cache key).  Every program is cached, however quick.
    With ``use`` false the cache is neither read nor written: None."""
    import jax

    jax.config.update("jax_enable_compilation_cache", use)
    if not use:
        return None
    path = os.environ.get(CACHE_ENV) or str(Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def profile_options():
    """Device activity and the benchmark's own host spans, without the
    Python tracer (a span per Python call would swamp the host)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


class Reservoir:
    """A uniform sample of ``size`` requests' answers, drawn from the seed
    (Vitter's algorithm R), whatever the window's length."""

    def __init__(self, size: int, seed: int):
        from benchmarks.chip.traffic import STREAM_SAMPLE

        self.size = size
        self.rng = np.random.default_rng([seed, STREAM_SAMPLE])
        self.items = []
        self.seen = 0

    def offer(self, pool_i: int, answer: np.ndarray) -> None:
        if self.seen < self.size:
            self.items.append((pool_i, answer))
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = (pool_i, answer)
        self.seen += 1


class CompileCounter:
    """Counts the programs JAX lowers (traced and compiled, or fetched
    from the compile cache) while ``active``: a warm window lowers none."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if self.active and event == self.EVENT:
            self.count += 1


def run(workload: str, seed: int, seconds: float, trace: bool, *, root: Path = ROOT,
        require_chip: bool = True, entry_module=None, out=sys.stdout) -> dict:
    """One run of one cell; returns the result line's object."""
    from benchmarks.chip import manifest, readers, reference, traffic
    from benchmarks.chip import trace as tracemod
    from benchmarks.chip.peaks import peaks
    from benchmarks.chip.resident import peak_bytes

    cell = manifest.cell(root, workload)
    cfg, mix = cell["config"], cell["mix"]
    chips = int(cell["workload"]["chips"])
    import jax

    devices = jax.devices()
    if require_chip:
        require_chips(devices, chips)
        peaks(devices[0].device_kind)  # an unknown chip is an error before anything is built
    used = devices[:chips]
    jax.config.update("jax_enable_x64", True)
    cache = compile_cache(root, use=not trace)
    dev = used[0]
    emit(out, "device", platform=dev.platform, kind=dev.device_kind, count=len(devices),
         jax=jax.__version__, compile_cache=cache or "bypassed: a traced run compiles its scopes",
         compile_cache_entries=len(os.listdir(cache)) if cache and os.path.isdir(cache) else 0)

    from benchmarks.chip import data

    t = time.perf_counter()
    table = data.table(cfg["dataset"], int(cfg["keys"]), seed)
    timings = {"generate_s": time.perf_counter() - t}
    entry = (entry_module or cell["entry"]).build(cfg, table)
    timings.update(entry.timings)
    t = time.perf_counter()
    batches = traffic.pool(mix, cell["draw"], table, seed)
    timings["traffic_s"] = time.perf_counter() - t
    t = time.perf_counter()
    for i in range(WARMUP_CALLS):
        np.asarray(entry.call(batches[i % len(batches)]))
    timings["warmup_s"] = time.perf_counter() - t
    batch = int(mix["batch"])
    sample = Reservoir(max(64, SAMPLE_BYTES // (8 * batch)), seed)
    gc.collect()
    compiles = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir, profiler_options=profile_options())
    setup_s = time.perf_counter() - T_START
    if trace:
        from repro import obs

        counters_before = obs.snapshot()
    compiles.active = True
    try:
        lat, window_s = cell["loop"].drive(entry, batches, seconds, sample,
                                          traffic.loop_params(mix))
    finally:
        compiles.active = False
        if trace:
            jax.profiler.stop_trace()
    if trace:
        counters = readers.counter_increase(counters_before, obs.snapshot())
    peak = peak_bytes(used)
    model_bytes = entry.model_device_bytes()
    emit(out, "setup", entry=entry.name, keys=len(table), seed=seed, setup_s=setup_s, **timings,
         space_bytes=entry.space_bytes(), model_device_bytes=model_bytes)
    emit(out, "window", requests=len(lat), batch=batch, seconds=window_s,
         p50_ms=float(np.percentile(lat, 50)) * 1e3, p99_ms=float(np.percentile(lat, 99)) * 1e3,
         requests_beyond_p99=int(np.sum(lat > np.percentile(lat, 99))),
         first_ms=float(lat[0]) * 1e3, compiles_in_window=compiles.count,
         memory_peak_bytes=peak)

    # the check: the window's answers against the reference, once it has closed
    t = time.perf_counter()
    want = {}
    wrong = 0
    for k, ans in sample.items:
        if k not in want:
            want[k] = reference.predecessor_rank(table, batches[k])
        wrong += reference.wrong_answers(ans, want[k])
    checked = len(sample.items)
    emit(out, "check", requests_checked=checked, answers_checked=checked * batch,
         wrong_answers=wrong, seconds=time.perf_counter() - t)
    correct = wrong == 0
    attempted = len(lat) * batch

    if trace:
        names = tracemod.collected_spans(cell["per_layer"].values())
        try:
            chips_ev, spans, window, op_scopes = tracemod.load(tracemod.xplane_file(trace_dir), names)
            reduced = tracemod.reduce(chips_ev, spans, window, op_scopes, names) if window else {}
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        per_query = None
        if any(getattr(mod, "NEEDS_BYTES", False) for mod in cell["per_layer"].values()):
            ranks = np.concatenate([want[k] if k in want else reference.predecessor_rank(table, b)
                                    for k, b in enumerate(batches)])
            per_query = entry.needed_bytes(ranks)
        on_device = "busy_s" in reduced
        ctx = {
            "trace": reduced or None,
            "counters": counters,
            "requests": len(lat),
            "timings": timings,
            "needed_bytes_per_request": None if per_query is None else float(per_query.mean()) * batch,
            "peaks": peaks(dev.device_kind) if on_device else None,
        }
        values = {name: mod.read(ctx) for name, mod in cell["per_layer"].items()}
        emit(out, "trace", busy_s=reduced.get("busy_s"), window_s=reduced.get("window_s"),
             programs=reduced.get("programs"), scopes=reduced.get("scopes"),
             unscoped=reduced.get("unscoped"), unscoped_ops=reduced.get("unscoped_ops"),
             idle_by_span=reduced.get("idle_by_span"), span_s=reduced.get("span_s"),
             counters=[[n, dict(labels), v] for (n, labels), v in counters.items() if v],
             needed_bytes_per_request=ctx["needed_bytes_per_request"],
             roofline_bound="HBM bandwidth", peaks=ctx["peaks"])
    else:
        rows = len(table)
        values = {
            "ops_per_s": (attempted - wrong) / window_s,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3,
            "hbm_bytes_per_key": None if peak is None else peak / rows,
            "model_space_pct": 100.0 * model_bytes / (8 * rows),
            "setup_s": setup_s,
        }
        values = {k: values.get(k) for k in cell["end_to_end"]}
    metrics = {k: {"value": v, "unit": cell["units"][k]} for k, v in values.items() if v is not None}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": attempted, "failed": wrong,
              "metrics": metrics, "device": device}
    if trace and on_device:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {"wrong_answers": {"value": wrong, "limit": 0}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
