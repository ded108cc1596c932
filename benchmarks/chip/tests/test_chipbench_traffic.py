"""The data and traffic generators: the same seed gives the same batches,
and YCSB's scrambled zipfian keeps its published skew."""

from __future__ import annotations

import numpy as np
import pytest

from benchmarks.chip import data, manifest, traffic
from chipbench_fixtures import REPO

ZIPF = manifest.load_module(manifest.bench_file(REPO, "draws", "scrambled_zipfian", ".py"))

YCSB_C = {"loop": "closed", "batch": 4096, "pool_batches": 3,
          "keys": {"draw": "scrambled_zipfian", "theta": 0.99,
                   "item_space": 10_000_000_000, "zetan": 26.46902820178302}}
UNIFORM = {"loop": "closed", "batch": 4096, "pool_batches": 3, "keys": {"draw": "uniform_present"}}


@pytest.mark.parametrize("mix", [UNIFORM, YCSB_C], ids=["uniform", "ycsb-c"])
def test_same_seed_same_batches(mix):
    big = 2**31 + 17
    t = data.table("osm", 50_000, big)
    assert np.array_equal(t, data.table("osm", 50_000, big))
    _, draw = manifest.mix_modules(REPO, mix)
    assert traffic.problems(mix, *manifest.mix_modules(REPO, mix)) == []
    a, b = traffic.pool(mix, draw, t, big), traffic.pool(mix, draw, t, big)
    assert len(a) == 3 and all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], traffic.pool(mix, draw, t, big + 1)[0])
    assert all(np.isin(x, t).all() for x in a)  # every query key is present


@pytest.mark.parametrize("name", data.DATASETS)
def test_the_data_copy_matches_the_program_generator(name):
    from repro.data.distributions import generate

    assert np.array_equal(data.table(name, 20_000, 3), generate(name, 20_000, seed=3))


@pytest.mark.parametrize("change,complaint", [
    ({"callers": 4}, "callers = 4 is not implemented"),
    ({"read_share": 0.95}, "read_share = 0.95 is not implemented"),
    ({"think_ms": 5}, "takes no parameter 'think_ms'"),
    ({"batch": 0}, "batch must be"),
    ({"keys": {"draw": "scrambled_zipfian", "theta": 0.99}}, "needs 'item_space'"),
    ({"keys": {"draw": "uniform_present", "theta": 0.99}}, "takes no parameter 'theta'"),
])
def test_a_mix_key_the_harness_does_not_implement_is_refused(change, complaint):
    mix = {**YCSB_C, "callers": 1, "read_share": 1.0, **change}
    found = traffic.problems(mix, *manifest.mix_modules(REPO, mix))
    assert any(complaint in p for p in found), found


AHEAD = {**UNIFORM, "loop": "ahead", "callers": 1, "depth": 4}


@pytest.mark.parametrize("change,complaint", [
    ({"depth": 0}, "depth must be a whole number"),
    ({"depth": True}, "depth must be a whole number"),
    ({"callers": 2}, "callers = 2 is not implemented"),
    ({"think_ms": 5}, "takes no parameter 'think_ms'"),
])
def test_the_ahead_loop_refuses_what_it_does_not_implement(change, complaint):
    mix = {**AHEAD, **change}
    assert traffic.problems(AHEAD, *manifest.mix_modules(REPO, AHEAD)) == []
    found = traffic.problems(mix, *manifest.mix_modules(REPO, mix))
    assert any(complaint in p for p in found), found


class Recorder:
    """An entry whose answers are futures: it counts what is unanswered."""

    def __init__(self):
        self.sent = self.most_pending = 0
        self.read = []

    def call(self, q):
        self.sent += 1
        rec, i = self, self.sent

        class Answer:
            def __array__(self, dtype=None, copy=None):
                rec.read.append(i)
                return np.asarray(q)

        self.most_pending = max(self.most_pending, self.sent - len(self.read))
        return Answer()


class Kept:
    def __init__(self):
        self.items = []

    def offer(self, k, ans):
        self.items.append(k)


def test_the_ahead_loop_answers_every_request_it_sends_in_order():
    loop, _ = manifest.mix_modules(REPO, AHEAD)
    entry, kept = Recorder(), Kept()
    batches = [np.full(3, k, np.uint64) for k in range(5)]
    lat, window_s = loop.drive(entry, batches, 0.05, kept, traffic.loop_params(AHEAD))
    assert entry.read == list(range(1, entry.sent + 1)) and len(lat) == entry.sent > 4
    assert entry.most_pending == AHEAD["depth"]
    assert kept.items == [i % len(batches) for i in range(entry.sent)]
    assert window_s >= 0.05 and (lat > 0).all()


def test_scrambled_zipfian_rank_frequencies_follow_theta():
    rng = np.random.default_rng(1)
    ranks = ZIPF.zipfian_ranks(rng, 2_000_000, 0.99, 10_000_000_000, 26.46902820178302)
    pos = (ZIPF.fnv1a64(ranks) % np.uint64(1_000_000)).astype(np.int64)
    freq = np.sort(np.bincount(pos))[::-1] / len(pos)
    assert freq[0] == pytest.approx(1 / 26.46902820178302, rel=0.03)
    slope = np.polyfit(np.log(np.arange(1, 101)), np.log(freq[:100]), 1)[0]
    assert slope == pytest.approx(-0.99, abs=0.04)


def test_fnv_hash_is_ycsbs():
    # YCSB Utils.fnvhash64: FNV-1a over 8 bytes, low byte first, then abs
    h = np.uint64(0xCBF29CE484222325)
    v = 123456789
    for _ in range(8):
        h = np.uint64((int(h) ^ (v & 0xFF)) * 1099511628211 % 2**64)
        v >>= 8
    want = abs(int(np.int64(np.uint64(h).view(np.int64))))
    assert int(ZIPF.fnv1a64(np.array([123456789], np.uint64))[0]) == want
