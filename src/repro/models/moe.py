"""Mixture-of-Experts block: expert-parallel shard_map dispatch.

EP design (DESIGN.md §5): activations at block boundaries are replicated
over the ``model`` axis (the TP convention), so each model column routes
the *same* local-token set to its *own* E/ep experts, computes them, and
a psum over ``model`` assembles the block output — no token all-to-all
is needed and the collective cost equals the TP FFN reduction.  Expert
weights are additionally FSDP-sharded over the DP axes and all-gathered
per layer inside the block (manual ZeRO-3).

The capacity dispatch is **sort-based**: flatten (token, k) pairs, sort
by expert id, find each expert's boundary with the paper's branch-free
predecessor search over the sorted expert-id table (DESIGN.md §3,
integration point 2), then slot tokens with pure gathers.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import search


def _dispatch_local(x, gate_w, *, e_loc: int, col, n_experts: int, top_k: int,
                    capacity: int, dtype):
    """Route local tokens to this column's experts.

    x: (T, d) local tokens.  Returns (xe, combine) where
    xe: (E_loc, C, d) dispatched tokens and combine(ye) -> (T, d).
    """
    t, d = x.shape
    logits = jnp.einsum("td,de->te", x, gate_w.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, top_k)  # (T, k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1).astype(jnp.int32)  # (T*k,)
    flat_t = (
        lax.broadcasted_iota(jnp.int32, (t, top_k), 0).reshape(-1)
    )
    local = (flat_e >= col * e_loc) & (flat_e < (col + 1) * e_loc)
    # push non-local pairs to the end of the sort with a sentinel
    sort_key = jnp.where(local, flat_e - col * e_loc, n_experts + 1)
    order = jnp.argsort(sort_key)
    s_key = jnp.take(sort_key, order)
    s_tok = jnp.take(flat_t, order)

    # expert boundaries via the paper's branch-free predecessor search
    eq = jnp.arange(e_loc, dtype=jnp.int32)
    bounds = search.bfs(s_key, eq - 1) + 1  # first sorted pos of each local expert
    ends = search.bfs(s_key, eq) + 1

    # slot gather: expert e takes sorted positions [bounds[e], bounds[e]+C)
    slots = bounds[:, None] + lax.broadcasted_iota(jnp.int32, (e_loc, capacity), 1)
    valid = slots < ends[:, None]
    tok_idx = jnp.take(s_tok, jnp.minimum(slots, t * top_k - 1))
    xe = jnp.take(x, tok_idx, axis=0) * valid[..., None].astype(x.dtype)  # (E_loc, C, d)

    # combine indices: position of each (t, k) pair within its expert
    pos_sorted = (
        lax.broadcasted_iota(jnp.int32, (t * top_k,), 0)
        - jnp.take(bounds, jnp.clip(s_key, 0, e_loc - 1))
    )
    inv = jnp.argsort(order)
    pos = jnp.take(pos_sorted, inv)  # (T*k,) position-in-expert
    keep = local & (pos < capacity)
    le = jnp.clip(flat_e - col * e_loc, 0, e_loc - 1)

    def combine(ye):  # ye: (E_loc, C, d)
        flat_pos = jnp.clip(pos, 0, capacity - 1)
        vecs = ye[le, flat_pos]  # (T*k, d) gather
        w = (top_p.reshape(-1).astype(ye.dtype) * keep.astype(ye.dtype))[:, None]
        contrib = (vecs * w).reshape(t, top_k, d)
        return jnp.sum(contrib, axis=1)

    return xe, combine


def moe_ffn(x2d, moe_params, cfg, ctx, *, replicated_tokens: bool = False):
    """x2d: (T, d) replicated over 'model', sharded over DP axes.

    moe_params: {'router': (d, E), 'wg','wu': (E, d, ffe), 'wd': (E, ffe, d)}.
    Returns (T, d).  ``replicated_tokens`` handles tiny decode batches
    (e.g. long_500k with batch=1) that cannot shard over DP.
    """
    mesh = ctx.mesh
    dp_axes = () if replicated_tokens else (ctx.rules["dp"] or ())
    ep_axes = ctx.rules["ep"] or ()
    dp_size = 1 if replicated_tokens else ctx.n("dp")
    ep_size = ctx.n("ep")
    e_loc = cfg.n_experts // ep_size
    t_loc = x2d.shape[0] // dp_size
    capacity = max(1, int(math.ceil(t_loc * cfg.top_k / cfg.n_experts * cfg.capacity_factor)))
    dtype = x2d.dtype

    dp_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    ep_spec = ep_axes[0] if ep_axes else None
    fsdp_axes = ctx.rules["dp"] or ()  # weights stay FSDP-sharded regardless

    def block(x, wr, wg, wu, wd):
        # x: (T_loc, d); wr replicated; w*: (E_loc, d/fsdp, ffe) shards.
        # §Perf iteration A: cast the FSDP shards to the compute dtype
        # BEFORE the all-gather — halves the dominant AG traffic.
        if fsdp_axes:
            wg = lax.all_gather(wg.astype(dtype), fsdp_axes, axis=1, tiled=True)
            wu = lax.all_gather(wu.astype(dtype), fsdp_axes, axis=1, tiled=True)
            wd = lax.all_gather(wd.astype(dtype), fsdp_axes, axis=2, tiled=True)
        col = lax.axis_index(ep_axes[0]) if ep_axes else 0
        xe, combine = _dispatch_local(
            x, wr, e_loc=e_loc, col=col, n_experts=cfg.n_experts,
            top_k=cfg.top_k, capacity=capacity, dtype=dtype,
        )
        g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(dtype))
        u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(dtype))
        h = jax.nn.silu(g) * u
        ye = jnp.einsum("ecf,efd->ecd", h, wd.astype(dtype))
        y = combine(ye)
        if ep_axes:
            y = lax.psum(y, ep_axes)
        return y

    fsdp_spec = fsdp_axes if len(fsdp_axes) > 1 else (fsdp_axes[0] if fsdp_axes else None)
    return jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(
            P(dp_spec, None),          # x (T, d)
            P(None, None),             # router
            P(ep_spec, fsdp_spec, None),  # wg (E, d, ffe)
            P(ep_spec, fsdp_spec, None),  # wu
            P(ep_spec, None, fsdp_spec),  # wd (E, ffe, d)
        ),
        out_specs=P(dp_spec, None),
        check_vma=False,
    )(x2d, moe_params["router"], moe_params["wg"], moe_params["wu"], moe_params["wd"])
