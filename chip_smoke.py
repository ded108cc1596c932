#!/usr/bin/env python3
"""Chip smoke test: the learned-index serving path on a TPU at SOSD scale.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # the sharded tier over four chips

One chip: a 200M-key uint64 table (SOSD's size, 1.6 GB) is generated from
a seed and placed on the device once.  SY-RMI and PGM_M at the paper's
0.05% space point and RS are built through ``repro.index.build`` and
answer four batches of 65,536 queries (90% hits, 10% uniform keys)
through ``Index.lookup(..., backend="xla")``.  A 4-shard PGM_M
``TunedTier`` then answers the same batches on the one chip (mode
``ref``).

``--four-chips``: a 4-shard ``ShardedIndex`` (PGM_M and SY-RMI) is laid
out over a (1, 4) mesh, one shard per chip, and answers through
``sharded_lookup`` in ``a2a`` and ``allgather`` mode.

Every answer must equal ``np.searchsorted(table, q, side="right") - 1``;
``DROPPED`` is allowed only from ``a2a`` at the default capacity factor,
where it is counted.  Earlier lines of stdout are JSON records, one per
phase; the last line is ``{"ok": true, "device": {...}}``.  Without a
TPU, or with any mismatch or error, the script exits non-zero before
building anything or before that line.  It runs in one process.
"""

from __future__ import annotations

import argparse
import json
import sys
from importlib import metadata
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SOSD_KEYS = 200_000_000
MIN_KEYS = 50_000_000
SEED = 0
BATCHES = 4
BATCH = 65_536
HIT_FRAC = 0.9
#: (kind, params): SY-RMI and PGM_M at the paper's 0.05% space point
KINDS = (("SY-RMI", {"space_pct": 0.05}), ("PGM_M", {"space_pct": 0.05}), ("RS", {}))
TIER_KIND = KINDS[1]
SHARDED_KINDS = (KINDS[1], KINDS[0])
N_SHARDS = 4


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require_tpu() -> list:
    """The devices, or exit non-zero when JAX finds no TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {devices[0].platform!r}); nothing was built")
    return devices


def mem(device, stat: str = "peak_bytes_in_use") -> int:
    return int(device.memory_stats()[stat])


def make_batches(table: np.ndarray, rng: np.random.Generator) -> list:
    """Query batches: HIT_FRAC keys drawn from the table, the rest
    uniform over the uint64 range, shuffled."""
    batches = []
    for _ in range(BATCHES):
        hits = rng.choice(table, int(BATCH * HIT_FRAC))
        rest = rng.integers(0, 2**64 - 1, BATCH - len(hits), dtype=np.uint64, endpoint=True)
        q = np.concatenate([hits, rest])
        rng.shuffle(q)
        batches.append(q)
    return batches


def reference(table: np.ndarray, q: np.ndarray) -> np.ndarray:
    return np.searchsorted(table, q, side="right").astype(np.int64) - 1


def one_chip(table, batches, wants, device) -> None:
    import jax
    from repro import index as ix
    from repro.index import registry
    from repro.obs.timing import stopwatch
    from repro.tune.rebuild import TunedTier

    sw = stopwatch()
    table_d = jax.device_put(table, device)
    table_d.block_until_ready()
    emit("place", keys=len(table), bytes=int(table.nbytes), seconds=sw.elapsed)

    failed = []
    for kind, params in KINDS:
        sw.restart()
        idx = ix.build(kind, table, **params)
        build_s = sw.elapsed
        sw.restart()  # first batch: trace + compile + run
        got = [np.asarray(idx.lookup(table_d, batches[0], backend="xla"))]
        compile_s = sw.elapsed
        got += [np.asarray(idx.lookup(table_d, q, backend="xla")) for q in batches[1:]]
        bad = sum(int((g != w).sum()) for g, w in zip(got, wants))
        emit(
            "lookup",
            kind=kind,
            index=idx.name,
            space_bytes=idx.space_bytes(),
            backend="xla",
            queries=BATCHES * BATCH,
            build_s=build_s,
            compile_s=compile_s,
            mismatches=bad,
            peak_bytes_in_use=mem(device),
        )
        if bad:
            failed.append(kind)
        del idx
    del table_d

    kind, params = TIER_KIND
    sw.restart()
    tier = TunedTier(table, n_shards=N_SHARDS, spec=registry.spec_for(kind, **params))
    build_s = sw.elapsed
    sw.restart()
    got = [np.asarray(tier.lookup(batches[0]))]
    compile_s = sw.elapsed
    got += [np.asarray(tier.lookup(q)) for q in batches[1:]]
    bad = sum(int((g != w).sum()) for g, w in zip(got, wants))
    emit(
        "tier",
        spec=tier.spec.display_name(),
        n_shards=tier.sidx.n_shards,
        mode="ref",
        backend=tier.policy.backend,
        queries=BATCHES * BATCH,
        build_s=build_s,
        compile_s=compile_s,
        mismatches=bad,
        peak_bytes_in_use=mem(device),
    )
    if bad:
        failed.append("tier")
    if failed:
        sys.exit(f"chip_smoke: answers differ from the reference for {failed}")


def four_chips(table, batches, wants, devices) -> None:
    import jax
    from repro.dist.sharded_index import DROPPED, ShardedIndex, sharded_lookup
    from repro.dist.sharding import ShardingCtx, make_mesh
    from repro.obs.timing import stopwatch

    if len(devices) < N_SHARDS:
        sys.exit(f"chip_smoke: --four-chips needs {N_SHARDS} chips, JAX found {len(devices)}")
    ctx = ShardingCtx(mesh=make_mesh((1, N_SHARDS), ("data", "model"), devices=devices[:N_SHARDS]))
    failed = []
    sw = stopwatch()
    for kind, params in SHARDED_KINDS:
        sw.restart()
        sidx = ShardedIndex.build(kind, table, n_shards=N_SHARDS, **params).place(ctx)
        jax.block_until_ready(sidx)
        build_s = sw.elapsed
        # every stacked leaf is split by shard: each chip must hold a quarter
        stacked = [
            *jax.tree_util.tree_leaves(sidx.tables), sidx.counts, sidx.offsets,
            *jax.tree_util.tree_leaves(sidx.index),
        ]
        tier_bytes = sum(int(x.nbytes) for x in stacked)
        held = {d.id: 0 for d in ctx.mesh.devices.flat}
        for x in stacked:
            for s in x.addressable_shards:
                held[s.device.id] += int(s.data.nbytes)
        emit(
            "sharded_build",
            kind=kind,
            n_shards=N_SHARDS,
            build_s=build_s,
            tier_bytes=tier_bytes,
            tier_bytes_per_device=held,
            bytes_in_use_per_device={d.id: mem(d, "bytes_in_use") for d in devices},
        )
        if any(4 * b != tier_bytes for b in held.values()):
            failed.append(f"{kind}: tier bytes per device {held} are not a quarter of {tier_bytes}")
        for mode, cap_factor in (("a2a", 2.0), ("a2a", 4.0), ("allgather", 2.0)):
            sw.restart()
            got = [
                np.asarray(sharded_lookup(sidx, q, ctx, mode=mode, cap_factor=cap_factor))
                for q in batches
            ]
            seconds = sw.elapsed
            dropped = sum(int((g == DROPPED).sum()) for g in got)
            wrong = sum(int(((g != w) & (g != DROPPED)).sum()) for g, w in zip(got, wants))
            emit(
                "sharded_lookup",
                kind=kind,
                mode=mode,
                cap_factor=cap_factor,
                queries=BATCHES * BATCH,
                seconds_incl_compile=seconds,
                mismatches=wrong,
                dropped=dropped,
                peak_bytes_in_use_per_device={d.id: mem(d) for d in devices},
            )
            # drops are legal only from a2a at the default capacity factor
            if wrong or (dropped and not (mode == "a2a" and cap_factor == 2.0)):
                failed.append(f"{kind}/{mode}/cap_factor={cap_factor}")
        del sidx
    if failed:
        sys.exit(f"chip_smoke: four-chip phase failed: {failed}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true", help="run only the 4-chip sharded phase")
    ap.add_argument("--keys", type=int, default=SOSD_KEYS, help=f"table size (>= {MIN_KEYS})")
    args = ap.parse_args(argv)
    if args.keys < MIN_KEYS:
        ap.error(f"--keys must be at least {MIN_KEYS}")

    devices = require_tpu()
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jaxlib
    from repro.data.distributions import generate
    from repro.launch.cache import enable_compile_cache
    from repro.obs.timing import stopwatch

    cache = Path(enable_compile_cache(ROOT))
    emit(
        "device",
        platform=devices[0].platform,
        kind=devices[0].device_kind,
        count=len(devices),
        jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        libtpu=metadata.version("libtpu"),
        compile_cache=str(cache),
        compile_cache_warm=cache.is_dir() and any(cache.iterdir()),
    )
    if args.keys < SOSD_KEYS:
        emit("reduced", keys=args.keys, sosd_keys=SOSD_KEYS, why="table size set by --keys")
    total = stopwatch()
    sw = stopwatch()
    table = generate("osm", args.keys, seed=SEED)
    rng = np.random.default_rng(SEED)
    batches = make_batches(table, rng)
    wants = [reference(table, q) for q in batches]
    emit("data", dataset="osm", keys=len(table), seed=SEED, seconds=sw.elapsed)

    if args.four_chips:
        four_chips(table, batches, wants, devices)
    else:
        one_chip(table, batches, wants, devices[0])
    emit("done", seconds=total.elapsed)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
