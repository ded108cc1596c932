"""Multi-device semantics on 8 fake CPU devices (subprocess so the main
test process keeps its single-device view).

Checks: sharded train step == single-device step (DP+TP correctness),
MoE shard_map dispatch == dense reference, elastic checkpoint restore
across mesh shapes, a2a embedding lookup == allreduce lookup.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
import repro
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.dist.sharding import ShardingCtx, make_mesh
from repro.configs import get as get_arch
from repro.launch import steps
from repro.models import transformer, recsys
from repro.train import TrainConfig, init_train_state, make_train_step, checkpoint

assert len(jax.devices()) == 8
mesh = make_mesh((4, 2), ("data", "model"))
ctx = ShardingCtx(mesh=mesh)
mesh1 = make_mesh((1, 1), ("data", "model"))
ctx1 = ShardingCtx(mesh=mesh1)

# ---- 1. sharded vs single-device LM train step ----
import dataclasses
spec = get_arch("moonshot-v1-16b-a3b", reduced=True)  # exercises MoE EP
# no-drop capacity: capacity depends on per-shard token counts, so token
# dropping would (legitimately) differ across mesh shapes
spec = dataclasses.replace(spec, config=dataclasses.replace(spec.config, capacity_factor=16.0))
cell = spec.shapes[0]
tcfg = TrainConfig(lr=1e-3, schedule="constant")
rng = jax.random.key(0)

def run(ctx_, mesh_):
    cfg = spec.config
    from functools import partial
    loss = lambda p, b: transformer.loss_fn(p, b, cfg, ctx_)
    step = make_train_step(loss, tcfg)
    init_fn = lambda r: transformer.init(r, cfg)
    state = init_train_state(rng, init_fn, tcfg)
    batch = steps.make_inputs(spec, cell, abstract=False)
    with mesh_:
        state, metrics = jax.jit(step)(state, batch)
    return float(metrics["loss"]), state

l8, st8 = run(ctx, mesh)
l1, st1 = run(ctx1, mesh1)
assert abs(l8 - l1) < 2e-2, (l8, l1)
w8 = np.asarray(jax.tree_util.tree_leaves(st8["params"])[0], np.float32)
w1 = np.asarray(jax.tree_util.tree_leaves(st1["params"])[0], np.float32)
np.testing.assert_allclose(w8, w1, rtol=2e-2, atol=2e-3)
print("OK sharded==single LM+MoE train step")

# ---- 2. elastic checkpoint: save on (4,2), restore on (2,4) ----
import tempfile
d = tempfile.mkdtemp()
checkpoint.save(d, st8, step=1, async_write=False)
mesh_b = make_mesh((2, 4), ("data", "model"))
ctx_b = ShardingCtx(mesh=mesh_b)
sh = steps.state_shardings(st8, "lm", ctx_b)
sh = steps.fit_tree(jax.eval_shape(lambda: st8), sh, mesh_b)
restored, _ = checkpoint.restore(d, st8, shardings=sh)
r0 = np.asarray(jax.tree_util.tree_leaves(restored["params"])[0], np.float32)
np.testing.assert_allclose(r0, w8, rtol=1e-6)
print("OK elastic restore across mesh shapes")

# ---- 3. a2a embedding lookup == allreduce lookup ----
from repro.models.embedding import sharded_lookup
rng2 = np.random.default_rng(0)
table = jnp.asarray(rng2.normal(size=(64, 8)).astype(np.float32))
ids = jnp.asarray(rng2.integers(0, 64, size=(16, 3)).astype(np.int32))
with mesh:
    out_ar = jax.jit(lambda t, i: sharded_lookup(t, i, ctx, mode="allreduce"))(table, ids)
    out_a2a = jax.jit(lambda t, i: sharded_lookup(t, i, ctx, mode="a2a", cap_factor=16.0))(
        table, ids
    )
np.testing.assert_allclose(np.asarray(out_ar), np.asarray(out_a2a), rtol=1e-5, atol=1e-6)
print("OK a2a == allreduce embedding lookup")

# ---- 4. decode step under sharding ----
spec2 = get_arch("granite-3-8b", reduced=True)
cell2 = [c for c in spec2.shapes if c.name == "decode_32k"][0]
cfg2 = spec2.config
params2 = transformer.init(jax.random.key(1), cfg2)
cache2 = transformer.init_cache(cfg2, cell2.dims["global_batch"], cell2.dims["seq_len"])
batch2 = steps.make_inputs(spec2, cell2, abstract=False)
with mesh:
    lg8, _ = jax.jit(lambda p, c, b, s: transformer.decode_step(p, c, b["tokens"], s, cfg2, ctx))(
        params2, cache2, batch2, jnp.int32(3)
    )
with mesh1:
    lg1, _ = jax.jit(
        lambda p, c, b, s: transformer.decode_step(p, c, b["tokens"], s, cfg2, ctx1)
    )(params2, cache2, batch2, jnp.int32(3))
np.testing.assert_allclose(np.asarray(lg8), np.asarray(lg1), rtol=5e-2, atol=5e-2)
print("OK decode step sharded == single")
print("ALL MULTIDEVICE OK")
"""


@pytest.mark.slow
def test_multidevice_semantics(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"  # fake CPU devices by design; never the chip
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True, timeout=1200
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr[-4000:]}"
    assert "ALL MULTIDEVICE OK" in res.stdout
