"""Production train launcher:  python -m repro.launch.train --arch <id>

Wires mesh + sharding profile + data pipeline + fault-tolerant loop for
any registered architecture.  On this container use ``--reduced`` (the
full configs need the fleet; their compile-only path is dryrun.py).
Exports the collective-overlap XLA flags a real fleet launch would set.
"""

from __future__ import annotations

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--cell", default=None, help="shape cell (default: the train cell)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-compression", default="none", choices=["none", "bf16", "int8"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--print-xla-flags", action="store_true")
    args = ap.parse_args()

    if args.print_xla_flags:
        from repro.dist.collectives import OVERLAP_XLA_FLAGS

        print(OVERLAP_XLA_FLAGS)
        return

    import jax
    import repro  # noqa: F401
    from repro import configs
    from repro.dist.sharding import ShardingCtx, make_mesh, single_device_ctx
    from repro.launch import steps
    from repro.train import TrainConfig, init_train_state, loop

    spec = configs.get(args.arch, reduced=args.reduced)
    cells = [c for c in spec.shapes if c.kind in ("train", "graph_train")]
    cell = next((c for c in cells if c.name == args.cell), cells[0])

    n_dev = len(jax.devices())
    if n_dev == 1:
        ctx = single_device_ctx()
    else:
        from repro.launch.dryrun import profile_for
        import math

        d = int(math.sqrt(n_dev))
        mesh = make_mesh((n_dev // d, d), ("data", "model"))
        ctx = ShardingCtx(mesh=mesh, profile=profile_for(spec))

    tcfg = TrainConfig(
        lr=args.lr,
        total_steps=args.steps,
        grad_compression=args.grad_compression,
        microbatches=args.microbatches,
    )
    bundle = steps.build_step(spec, cell, ctx, tcfg)

    def batch_at(step):
        return steps.make_inputs(spec, cell, abstract=False, rng=np.random.default_rng(step))

    from repro.models import dimenet, recsys, transformer

    if spec.family == "lm":
        init_fn = lambda r: transformer.init(r, bundle.extra["cfg"])
    elif spec.family == "gnn":
        init_fn = lambda r: dimenet.init(r, bundle.extra["cfg"])
    else:
        init_fn = lambda r: recsys.init(r, bundle.extra["cfg"], ctx)

    state = init_train_state(jax.random.key(0), init_fn, tcfg)
    step_fn = jax.jit(bundle.fn)
    with ctx.mesh:
        state, report = loop.run(
            step_fn, state, batch_at,
            loop.LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir, ckpt_every=50),
        )
    print(f"[train] done: {report.steps_run} steps, final loss {report.losses[-1]:.4f}")


if __name__ == "__main__":
    main()
