"""``correct`` on the CPU at a small size: a sound run passes, and the
control and each fault a cell can have come out not correct.  These
runs skip the harness's look for a chip and drive the rest of a run."""

from __future__ import annotations

import io

import numpy as np
import pytest

from benchmarks.chip import manifest, run
from chipbench_fixtures import small_root

CELLS = ["tiny-syrmi.tiny-uniform", "tiny-pgmm-tier4.tiny-zipf"]
SEED = 2**31 + 99


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(tmp_path_factory.mktemp("bench"))


def run_with(root, cell, wrap=None, entry_module=None, trace=False):
    c = manifest.cell(root, cell)
    mod = entry_module or c["entry"]
    if wrap is not None:
        real = mod

        class Broken:
            @staticmethod
            def build(cfg, table):
                e = real.build(cfg, table)
                inner = e.call
                e.call = lambda q: wrap(inner(q))
                return e

        mod = Broken
    return run.run(cell, SEED, 0.3, trace, root=root, require_chip=False,
                   entry_module=mod, out=io.StringIO())


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(root, cell):
    r = run_with(root, cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"] == {"wrong_answers": {"value": 0, "limit": 0}}
    assert list(r)[-1] == "checks"
    assert {"ops_per_s", "model_space_pct", "setup_s"} <= set(r["metrics"])
    # the tail is the closed loop's; the bulk cell keeps requests in flight
    assert ("p99_ms" in r["metrics"]) == (cell == CELLS[1])


def one_answer_altered(out):
    return out.at[0].add(1)


def half_the_batch_left_out(out):
    half = out.shape[0] // 2
    return out.at[half:].set(-1)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [one_answer_altered, half_the_batch_left_out])
def test_a_fault_in_the_timed_path_is_not_correct(root, cell, fault):
    r = run_with(root, cell, wrap=fault)
    assert not r["correct"]
    assert r["checks"]["wrong_answers"]["value"] > r["checks"]["wrong_answers"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(root, cell):
    control = manifest.load_module(manifest.bench_file(root, "entries", "control_short_search", ".py"))
    r = run_with(root, cell, entry_module=control)
    assert not r["correct"]
    assert r["checks"]["wrong_answers"]["value"] > r["checks"]["wrong_answers"]["limit"]


def test_a_traced_run_reports_only_what_it_can_read(root):
    r = run_with(root, CELLS[0], trace=True)
    assert r["correct"]
    # the CPU has no device plane: only the set-up reader finds something
    assert set(r["metrics"]) == {"build_s"}


def test_answers_are_compared_with_the_reference():
    from benchmarks.chip import reference

    t = np.array([10, 20, 30], np.uint64)
    q = np.array([5, 10, 25, 30, 99], np.uint64)
    want = reference.predecessor_rank(t, q)
    assert want.tolist() == [-1, 0, 1, 2, 2]
    assert reference.wrong_answers(want, want) == 0
    assert reference.wrong_answers(want[:3], want) == 2
    assert reference.wrong_answers(want + (q == 25), want) == 1
