"""``BENCHMARK.json``: its checks, and a cell resolved to its files.

Everything that belongs to one configuration, mix, entry or per-layer
metric sits in a file of its own, found by name:

    configs/<config>.json     sizes, kind, entry, guarantee (``file`` in the manifest)
    mixes/<traffic>.json      the traffic generator's parameters (``traffic.py``)
    loops/<loop>.py           problems(params), drive(...): how a mix loads the system
    draws/<draw>.py           problems(params), positions(...): how a mix picks its keys
    entries/<entry>.py        build(config, table) -> Entry: the program's entry point
    layers/<metric>.py        read(ctx) -> float | None: one per-layer metric;
                              SPANS: the program's host spans it reads

so a later configuration, mix, loop, key draw, entry or metric is new
files plus new manifest entries, with no edit to a file that is here.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

from benchmarks.chip import needed_bytes, traffic

BENCH_DIR = Path("benchmarks") / "chip"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
E2E_SOURCES = ("device_trace", "host_clock")


def load(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def bench_file(root: Path, kind: str, name: str, suffix: str) -> Path:
    return Path(root) / BENCH_DIR / kind / f"{name}{suffix}"


def problems(root: Path, man: dict | None = None) -> list:
    """Every way ``BENCHMARK.json`` breaks the benchmark's own rules:
    names and units, sources, ``moves`` targets, and the files each
    name must resolve to.  Empty when the manifest is sound."""
    root = Path(root)
    man = load(root) if man is None else man
    out = []
    configs = {c["name"]: c for c in man["configs"]}
    cells = {w["name"]: w for w in man["workloads"]}
    e2e = {m["name"]: m for m in man["end_to_end"]}
    layers = {m["name"]: m for m in man["per_layer"]}
    for kind, items in (("config", configs), ("cell", cells), ("metric", {**e2e, **layers})):
        for name in items:
            if not NAME.match(name):
                out.append(f"{kind} name {name!r} breaks the name rule")
    if len(e2e) + len(layers) != len(man["end_to_end"]) + len(man["per_layer"]):
        out.append("two metrics share a name")
    for m in [*man["end_to_end"], *man["per_layer"]]:
        if not UNIT.match(m["unit"]):
            out.append(f"{m['name']}: unit {m['unit']!r} breaks the unit rule")
        if m["better"] not in ("lower", "higher"):
            out.append(f"{m['name']}: better must be lower or higher")
        for w in m.get("workloads", []):
            if w not in cells:
                out.append(f"{m['name']}: lists unknown cell {w!r}")
    for m in man["end_to_end"]:
        if m["source"] not in E2E_SOURCES:
            out.append(f"{m['name']}: an end-to-end source must be one of {E2E_SOURCES}")
    if "setup_s" not in e2e:
        out.append("setup_s is missing from end_to_end")
    for m in man["per_layer"]:
        if m["source"] not in SOURCES:
            out.append(f"{m['name']}: unknown source {m['source']!r}")
        if m["moves"] not in e2e:
            out.append(f"{m['name']}: moves unknown metric {m['moves']!r}")
        reader = bench_file(root, "layers", m["name"], ".py")
        if not reader.is_file():
            out.append(f"{m['name']}: no reader layers/{m['name']}.py")
            continue
        spans = getattr(load_module(reader), "SPANS", ())
        if not (isinstance(spans, tuple) and all(isinstance(n, str) and NAME.match(n) for n in spans)):
            out.append(f"{m['name']}: SPANS must be a tuple of span names, not {spans!r}")
    for name, c in configs.items():
        path = root / c["file"]
        if not path.is_file():
            out.append(f"config {name}: no file {c['file']}")
            continue
        cfg = json.loads(path.read_text())
        if not bench_file(root, "entries", cfg["entry"], ".py").is_file():
            out.append(f"config {name}: no entry entries/{cfg['entry']}.py")
        if not any(w["config"] == name for w in cells.values()):
            out.append(f"config {name}: used by no cell")
    for name, w in cells.items():
        if w["config"] not in configs:
            out.append(f"cell {name}: unknown config {w['config']!r}")
        if not NAME.match(w["traffic"]):
            out.append(f"cell {name}: traffic name {w['traffic']!r} breaks the name rule")
        out += [f"cell {name}: {p}" for p in mix_problems(root, w["traffic"])]
        if w["chips"] not in (1, 4):
            out.append(f"cell {name}: chips must be 1 or 4")
        reported = set(cell_metrics(man, name, "end_to_end"))
        kind = config_kind(root, configs.get(w["config"]))
        for m in cell_metrics(man, name, "per_layer"):
            if layers[m]["moves"] not in reported:
                out.append(f"cell {name}: {m} moves {layers[m]['moves']}, which it does not report")
            reader = bench_file(root, "layers", m, ".py")
            if (reader.is_file() and getattr(load_module(reader), "NEEDS_BYTES", False)
                    and kind is not None and kind not in needed_bytes.KINDS):
                out.append(f"cell {name}: {m} needs the bytes a lookup needs, and "
                           f"needed_bytes.py does not count kind {kind!r}")
    return out


def config_kind(root: Path, conf: dict | None):
    """The index kind a manifest configuration entry's file states, or None."""
    path = None if conf is None else Path(root) / conf["file"]
    return json.loads(path.read_text()).get("kind") if path and path.is_file() else None


def mix_problems(root: Path, traffic_name: str) -> list:
    """The mix file, its loop and key-draw files, and every key it states."""
    path = bench_file(root, "mixes", traffic_name, ".json")
    if not path.is_file():
        return [f"no mix mixes/{traffic_name}.json"]
    mix = json.loads(path.read_text())
    names = {"loops": mix.get("loop"), "draws": (mix.get("keys") or {}).get("draw")}
    missing = [f"mix {traffic_name}: no {d}/{n}.py" for d, n in names.items()
               if not (isinstance(n, str) and NAME.match(n) and bench_file(root, d, n, ".py").is_file())]
    if missing:
        return missing
    loop, draw = mix_modules(root, mix)
    return [f"mix {traffic_name}: {p}" for p in traffic.problems(mix, loop, draw)]


def mix_modules(root: Path, mix: dict) -> tuple:
    """The mix's loop and key-draw modules."""
    return (load_module(bench_file(root, "loops", mix["loop"], ".py")),
            load_module(bench_file(root, "draws", mix["keys"]["draw"], ".py")))


def cell_metrics(man: dict, cell: str, group: str) -> list:
    """Names of the ``group`` metrics that ``cell`` reports."""
    return [m["name"] for m in man[group] if cell in m.get("workloads", [cell])]


def load_module(path: Path):
    name = f"chipbench_{path.parent.name}_{path.stem}".replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(root: Path, workload: str) -> dict:
    """One cell with its configuration, mix, entry module and metrics."""
    root = Path(root)
    man = load(root)
    bad = problems(root, man)
    if bad:
        raise ValueError("BENCHMARK.json: " + "; ".join(bad))
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise ValueError(f"unknown workload {workload!r}; choose from {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in man["configs"] if c["name"] == w["config"])
    cfg = json.loads((root / conf["file"]).read_text())
    mix = json.loads(bench_file(root, "mixes", w["traffic"], ".json").read_text())
    loop, draw = mix_modules(root, mix)
    return {
        "workload": w,
        "config": cfg,
        "mix": mix,
        "loop": loop,
        "draw": draw,
        "entry": load_module(bench_file(root, "entries", cfg["entry"], ".py")),
        "end_to_end": cell_metrics(man, workload, "end_to_end"),
        "per_layer": {
            m: load_module(bench_file(root, "layers", m, ".py"))
            for m in cell_metrics(man, workload, "per_layer")
        },
        "units": {m["name"]: m["unit"] for m in [*man["end_to_end"], *man["per_layer"]]},
    }
