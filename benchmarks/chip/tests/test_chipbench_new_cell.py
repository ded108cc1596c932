"""A later change adds a cell as new files only: a configuration, a mix
whose loop is a new ``loops/<name>.py``, and a per-layer reader of its
own that declares a program span and reads a counter; in
``BENCHMARK.json`` it adds entries and appends the cell to a metric's
``workloads`` list.  On a copy of the benchmark so extended, the
manifest is sound, the small root builds, and the fixture tests pass."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from benchmarks.chip import manifest
from chipbench_fixtures import CELLS, REPO, small_root

NEW_CELL = "osm200m-syrmi-get.get-open"
NEW_METRIC = "get_front_batch_keys"

OPEN_LOOP = '''"""Loop ``open``: requests due at a fixed rate, whatever the answers do."""


def problems(params):
    rate = params.get("rate_per_s")
    bad = [] if isinstance(rate, (int, float)) and rate > 0 else [f"open loop: rate_per_s {rate!r}"]
    return bad + [f"open loop takes no parameter {k!r}" for k in params if k != "rate_per_s"]


def drive(entry, batches, seconds, sample, params):
    raise NotImplementedError("a stand-in for the loop a later change brings")
'''
READER = f'''"""{NEW_METRIC}: keys a coalesced batch of the get front, from its counters."""

from benchmarks.chip.readers import counter, per_request_ms, trace_value

SPANS = ("get.front",)


def read(ctx):
    keys, batches = counter(ctx, "get_front_keys"), counter(ctx, "get_front_batches")
    if not (keys and batches and (trace_value(ctx, "span_s") or {{}}).get("get.front")):
        return None
    return keys / batches
'''


def files(root):
    return {p.relative_to(root): p.read_bytes() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def only_appends(before: dict, after: dict) -> bool:
    """Every entry of ``before`` is in ``after`` at its place, unchanged
    but for cells appended to its ``workloads`` list."""
    groups = ("configs", "workloads", "end_to_end", "per_layer")
    if {k: v for k, v in after.items() if k not in groups} != {
            k: v for k, v in before.items() if k not in groups}:
        return False
    for group in groups:
        entries = before[group]
        for old, new in zip(entries, after[group][:len(entries)], strict=True):
            listed = old.get("workloads")
            if ({k: v for k, v in new.items() if k != "workloads"}
                    != {k: v for k, v in old.items() if k != "workloads"}
                    or (listed is None) != ("workloads" not in new)
                    or (listed is not None and new["workloads"][:len(listed)] != listed)):
                return False
    return True


def add_a_cell(root):
    """The new files and manifest entries of a get cell, added to ``root``."""
    bench = root / "benchmarks" / "chip"
    cfg = json.loads((bench / "configs" / "osm200m-syrmi.json").read_text())
    (bench / "configs" / "osm200m-syrmi-get.json").write_text(
        json.dumps({**cfg, "name": "osm200m-syrmi-get"}))
    (bench / "mixes" / "get-open.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 20000, "batch": 1, "pool_batches": 64,
         "keys": {"draw": "uniform_present"}}))
    (bench / "loops" / "open.py").write_text(OPEN_LOOP)
    (bench / "layers" / f"{NEW_METRIC}.py").write_text(READER)
    man = manifest.load(root)
    man["configs"].append({**man["configs"][0], "name": "osm200m-syrmi-get",
                           "file": "benchmarks/chip/configs/osm200m-syrmi-get.json"})
    man["workloads"].append({"name": NEW_CELL, "config": "osm200m-syrmi-get", "traffic": "get-open",
                             "chips": 1, "why": "single-key gets through a coalescing front"})
    man["per_layer"].append({"name": NEW_METRIC, "unit": "keys", "better": "higher",
                             "source": "program_counter", "layer": "get front", "moves": "p99_ms",
                             "workloads": [NEW_CELL]})
    next(m for m in man["end_to_end"] if m["name"] == "p99_ms")["workloads"].append(NEW_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(man, indent=1))


def test_a_new_cell_loop_span_reader_and_counter_reader_are_new_files_only(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(REPO / "benchmarks" / "chip", copy / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", copy / "BENCHMARK.json")
    before, man_before = files(copy), manifest.load(copy)
    add_a_cell(copy)

    after = files(copy)
    changed = {p for p in before if after[p] != before[p]}
    assert changed == {copy.joinpath("BENCHMARK.json").relative_to(copy)}
    assert only_appends(man_before, manifest.load(copy))
    assert manifest.problems(copy) == []
    c = manifest.cell(copy, NEW_CELL)
    assert NEW_METRIC in c["per_layer"] and "p99_ms" in c["end_to_end"]

    small = manifest.load(small_root(tmp_path / "small", source=copy))
    assert [w["name"] for w in small["workloads"]] == list(CELLS)
    assert NEW_METRIC not in {m["name"] for m in small["per_layer"]}
    p99 = next(m for m in small["end_to_end"] if m["name"] == "p99_ms")
    assert p99["workloads"] == ["tiny-pgmm-tier4.tiny-zipf"]

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO / "src")}
    tests = copy / "benchmarks" / "chip" / "tests"
    p = subprocess.run([sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
                        str(tests / "test_chipbench_manifest.py"), str(tests / "test_chipbench_correct.py")],
                       cwd=copy, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    assert " passed" in p.stdout and "failed" not in p.stdout and "error" not in p.stdout
