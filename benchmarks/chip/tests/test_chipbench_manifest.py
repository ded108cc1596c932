"""BENCHMARK.json against the benchmark's own rules, and a new cell
added as new files only."""

from __future__ import annotations

import json

import numpy as np
import pytest

from benchmarks.chip import manifest
from chipbench_fixtures import REPO, small_root


def test_the_manifest_is_sound():
    assert manifest.problems(REPO) == []


def test_names_units_and_sources_keep_to_the_rules():
    man = manifest.load(REPO)
    names = [c["name"] for c in man["configs"]] + [w["name"] for w in man["workloads"]]
    names += [w["traffic"] for w in man["workloads"]]
    names += [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    assert all(manifest.NAME.match(n) for n in names)
    assert all(manifest.UNIT.match(m["unit"]) for m in man["end_to_end"] + man["per_layer"])
    assert {m["source"] for m in man["end_to_end"]} <= set(manifest.E2E_SOURCES)


def test_every_moves_target_is_reported_where_the_metric_is():
    man = manifest.load(REPO)
    for w in man["workloads"]:
        e2e = set(manifest.cell_metrics(man, w["name"], "end_to_end"))
        layers = manifest.cell_metrics(man, w["name"], "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2 and layers
        for m in man["per_layer"]:
            if m["name"] in layers:
                assert m["moves"] in e2e


def test_every_named_file_exists():
    man = manifest.load(REPO)
    for c in man["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert manifest.bench_file(REPO, "entries", cfg["entry"], ".py").is_file()
    for w in man["workloads"]:
        assert manifest.bench_file(REPO, "mixes", w["traffic"], ".json").is_file()
        mix = json.loads(manifest.bench_file(REPO, "mixes", w["traffic"], ".json").read_text())
        assert manifest.bench_file(REPO, "loops", mix["loop"], ".py").is_file()
        assert manifest.bench_file(REPO, "draws", mix["keys"]["draw"], ".py").is_file()
    for m in man["per_layer"]:
        assert manifest.bench_file(REPO, "layers", m["name"], ".py").is_file()


def test_a_new_config_and_mix_need_only_new_files(tmp_path):
    root = small_root(tmp_path)
    assert manifest.problems(root) == []
    c = manifest.cell(root, "tiny-syrmi.tiny-uniform")
    assert c["config"]["keys"] == 40_000 and c["mix"]["batch"] == 512
    assert set(c["per_layer"]) == {"build_s", "device_idle_pct", "lookup_device_ms",
                                   "lookup_roofline_pct"}
    assert c["end_to_end"] == ["ops_per_s", "hbm_bytes_per_key", "model_space_pct", "setup_s"]
    assert "p99_ms" in manifest.cell(root, "tiny-pgmm-tier4.tiny-zipf")["end_to_end"]


EVERY_TENTH = '''
import numpy as np


def problems(params):
    return [f"every_tenth takes no parameter {k!r}" for k in params]


def positions(rng, params, n, size):
    return (rng.integers(0, n // 10, size=size) * 10).astype(np.int64)
'''


def test_a_new_key_draw_needs_only_new_files(tmp_path):
    from benchmarks.chip import data, traffic

    root = small_root(tmp_path)
    bench = root / "benchmarks" / "chip"
    (bench / "draws" / "every_tenth.py").write_text(EVERY_TENTH)
    (bench / "mixes" / "tiny-tenth.json").write_text(json.dumps(
        {"loop": "closed", "batch": 64, "pool_batches": 2, "keys": {"draw": "every_tenth"}}))
    man = manifest.load(root)
    man["workloads"].append({"name": "tiny-syrmi.tiny-tenth", "config": "tiny-syrmi",
                             "traffic": "tiny-tenth", "chips": 1, "why": "CPU test"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    assert manifest.problems(root) == []
    c = manifest.cell(root, "tiny-syrmi.tiny-tenth")
    t = data.table("osm", 1_000, 1)
    (b,) = traffic.pool(c["mix"], c["draw"], t, 7)[:1]
    assert np.isin(b, t[::10]).all()


@pytest.mark.parametrize("breakage,complaint", [
    (lambda m: m["workloads"][0].update(traffic="no-such-mix"), "no mix"),
    (lambda m: m["per_layer"][0].update(name="no reader"), "breaks the name rule"),
    (lambda m: m["end_to_end"][0].update(unit="ops per s"), "unit rule"),
    (lambda m: m["per_layer"][1].update(moves="no_such_metric"), "moves unknown"),
    (lambda m: m["end_to_end"][0].update(workloads=["tiny-syrmi.tiny-uniform"]), "does not report"),
    (lambda m: m["workloads"][0].update(traffic="tiny-four-callers"), "callers = 4 is not implemented"),
    (lambda m: m["workloads"][0].update(traffic="tiny-no-loop"), "no loops/open.py"),
    (lambda m: m["workloads"][0].update(config="tiny-rs"), "does not count kind 'RS'"),
])
def test_a_broken_manifest_is_refused(tmp_path, breakage, complaint):
    root = small_root(tmp_path)
    bench = root / "benchmarks" / "chip"
    uniform = json.loads((bench / "mixes" / "tiny-uniform.json").read_text())
    (bench / "mixes" / "tiny-four-callers.json").write_text(json.dumps({**uniform, "callers": 4}))
    (bench / "mixes" / "tiny-no-loop.json").write_text(json.dumps({**uniform, "loop": "open"}))
    rs = json.loads((bench / "configs" / "tiny-syrmi.json").read_text())
    (bench / "configs" / "tiny-rs.json").write_text(json.dumps({**rs, "name": "tiny-rs", "kind": "RS"}))
    man = manifest.load(root)
    man["configs"].append({**man["configs"][0], "name": "tiny-rs",
                           "file": "benchmarks/chip/configs/tiny-rs.json"})
    breakage(man)
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    assert any(complaint in p for p in manifest.problems(root))
    with pytest.raises(ValueError):
        manifest.cell(root, "tiny-syrmi.tiny-uniform")
