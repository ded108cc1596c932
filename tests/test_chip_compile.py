"""Compiles of the serving path for a described TPU v5e (nothing runs).

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what the chip would
refuse — tiling, fast-memory limits, a program larger than HBM — at no
chip time.  The topology is described inside a fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.  Also here: ``chip_smoke.py`` refuses a CPU, and the
compile-cache helper's directory choice.
"""

import importlib.util
import os
import re
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import index as ix
from repro.core.limbs import LimbTable
from repro.data.distributions import generate
from repro.dist import sharded_index as si
from repro.dist.sharding import ShardingCtx
from repro.dist import collectives
from repro.kernels import ops as kernel_ops
from repro.launch.cache import enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]
HBM_BYTES = 16 * 10**9  # one v5e chip
SOSD_KEYS = 200_000_000
SHARD_ROWS = 1 << 26  # a 50M-key shard of the 200M table, padded to a power of two
BATCH = 65_536
KINDS = (("SY-RMI", {"space_pct": 0.05}), ("PGM_M", {"space_pct": 0.05}), ("RS", {}))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_on = jax.config.jax_enable_compilation_cache
    # described-chip compiles are written to a cache but can never be read back
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def small_table():
    return generate("osm", 1 << 16, seed=0)


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


def _limb_planes(shape, sharding) -> LimbTable:
    """The tier's resident tables as described shapes: two u32 planes."""
    plane = jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)
    return LimbTable(plane, plane)


def _large_splits(hlo_text: str, min_elems: int) -> list:
    """``X64Split`` custom calls whose result holds at least ``min_elems``
    words: the whole-table limb split of a u64 table operand."""
    out = []
    for line in hlo_text.splitlines():
        m = re.search(r"= u32\[([\d,]+)\][^=]*custom_call_target=\"X64Split", line)
        if m and int(np.prod([int(d) for d in m.group(1).split(",")])) >= min_elems:
            out.append(line.strip()[:120])
    return out


def _fits_one_chip(compiled) -> int:
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes
    assert used <= HBM_BYTES, f"{used} bytes do not fit one chip's {HBM_BYTES}"
    return used


@pytest.mark.parametrize("kind,params", KINDS, ids=[k for k, _ in KINDS])
def test_xla_lookup_compiles_at_sosd_size(one_chip, small_table, kind, params):
    idx = ix.build(kind, small_table, **params)
    table = jax.ShapeDtypeStruct((SOSD_KEYS,), jnp.uint64, sharding=one_chip)
    q = jax.ShapeDtypeStruct((BATCH,), jnp.uint64, sharding=one_chip)
    lookup = jax.jit(ix.lookup_impl, static_argnames="backend")
    compiled = lookup.lower(_shapes(idx, one_chip), table, q, backend="xla").compile()
    assert _fits_one_chip(compiled) >= SOSD_KEYS * 8


def _op_names(hlo_text: str, pattern: str) -> list:
    """``op_name`` metadata of the instructions whose line matches ``pattern``."""
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in hlo_text.splitlines() if re.search(pattern, line)]


def test_sy_rmi_lookup_scopes_at_sosd_size(one_chip, small_table):
    """The attribution the device trace relies on, in the program the
    chip runs: the bounded search's loop sits under ``search``, and the
    u64 table's limb split (``X64SplitHigh``/``Low`` of the table
    parameter) under neither scope."""
    idx = ix.build("SY-RMI", small_table, space_pct=0.05)
    table = jax.ShapeDtypeStruct((SOSD_KEYS,), jnp.uint64, sharding=one_chip)
    q = jax.ShapeDtypeStruct((16_384,), jnp.uint64, sharding=one_chip)
    text = ix.index._lookup_jit.lower(_shapes(idx, one_chip), table, q, backend="xla").compile().as_text()
    whiles = _op_names(text, r"^\s*(ROOT )?%while[.\d]* = ")
    assert whiles and all("/search/" in n for n in whiles), whiles
    splits = _op_names(text, rf"= u32\[{SOSD_KEYS}\].*custom_call_target=\"X64Split(High|Low)\"")
    assert sorted(splits) == ["table", "table"], splits


def test_vmapped_tier_compiles_at_sosd_size(one_chip, small_table):
    """The one-chip tier over resident u32 limb planes: the program
    splits no table-sized operand and needs no table-sized scratch (a
    u64 table operand costs two whole-table ``X64Split`` calls and about
    2.15 GB of temporaries on every call)."""
    sidx = si.ShardedIndex.build("PGM_M", small_table, n_shards=4, space_pct=0.05)
    shapes = _shapes(sidx, one_chip)
    tier = si.ShardedIndex(
        shapes.index,
        _limb_planes((4, SHARD_ROWS), one_chip),
        shapes.fences,
        shapes.counts,
        shapes.offsets,
    )
    q = jax.ShapeDtypeStruct((BATCH,), jnp.uint64, sharding=one_chip)
    compiled = si._lookup_vmapped.lower(tier, q, backend="xla").compile()
    assert _fits_one_chip(compiled) >= 4 * SHARD_ROWS * 8
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    assert _large_splits(compiled.as_text(), SHARD_ROWS) == []


def test_a2a_sharded_lookup_compiles_on_four_chips(topo, small_table):
    mesh = Mesh(
        np.asarray(topo.devices[:4]).reshape(1, 4), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )
    ctx = ShardingCtx(mesh=mesh)
    axes = ctx.mesh_axes("tp")
    sidx = si.ShardedIndex.build("PGM_M", small_table, n_shards=4, space_pct=0.05)
    by_shard = NamedSharding(mesh, P(axes[0]))
    shapes = _shapes(sidx, by_shard)
    tier = si.ShardedIndex(
        shapes.index,
        _limb_planes((4, SHARD_ROWS), by_shard),
        jax.ShapeDtypeStruct(sidx.fences.shape, sidx.fences.dtype, sharding=NamedSharding(mesh, P())),
        shapes.counts,
        shapes.offsets,
    )
    q = jax.ShapeDtypeStruct((BATCH,), jnp.uint64, sharding=by_shard)
    cap = collectives.exchange_capacity(BATCH // 4, 4, 2.0)
    compiled = si._lookup_a2a.lower(tier, q, mesh, axes, "xla", cap).compile()
    ma = compiled.memory_analysis()  # per device: one shard row, not the tier
    assert ma.argument_size_in_bytes < 2 * SHARD_ROWS * 8
    assert compiled.as_text().count("all-to-all") >= 2
    # the shard row's limb planes enter as they are: no split, no scratch copy
    assert ma.temp_size_in_bytes < 64 << 20
    assert _large_splits(compiled.as_text(), SHARD_ROWS) == []


@pytest.mark.parametrize("kind,params", KINDS, ids=[k for k, _ in KINDS])
def test_pallas_lookup_is_refused_not_replaced(monkeypatch, one_chip, small_table, kind, params):
    """No fused search kernel lowers for the chip yet (in-kernel 1-D
    gather).  A ``pallas`` request on a TPU must fail loudly there,
    never fall back to interpret mode or to ``xla``."""
    monkeypatch.setattr(kernel_ops, "_interpret", lambda: False)  # what a TPU host sees
    idx = ix.build(kind, small_table, **params)
    table = jax.ShapeDtypeStruct((1 << 16,), jnp.uint64, sharding=one_chip)
    q = jax.ShapeDtypeStruct((4096,), jnp.uint64, sharding=one_chip)
    lookup = jax.jit(ix.lookup_impl, static_argnames="backend")
    with pytest.raises(NotImplementedError, match="Only 2D gather is supported"):
        lookup.lower(_shapes(idx, one_chip), table, q, backend="pallas").compile()


def test_chip_smoke_refuses_a_cpu(monkeypatch, capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)

    def no_build(*a, **k):
        raise AssertionError("chip_smoke built data before its device check")

    monkeypatch.setattr("repro.data.distributions.generate", no_build)
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "given"))
    assert enable_compile_cache(tmp_path) == str(tmp_path / "given")
    assert jax.config.jax_compilation_cache_dir == before  # set by JAX, not here
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert enable_compile_cache(tmp_path) == str(tmp_path.resolve() / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path.resolve() / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
