"""DimeNet (directional message passing) — arXiv:2003.03123.

Kernel regime: triplet gather (kernel_taxonomy §GNN) — messages live on
*directed edges* and interact over (k->j->i) triplets with radial (RBF)
and angular (SBF) bases.  Message passing is built on
``jax.ops.segment_sum`` over edge/triplet index lists (JAX has no sparse
message-passing primitive — this IS part of the system).

Faithful pieces: embedding block, ``n_blocks`` interaction blocks with
the bilinear triplet contraction (n_bilinear), per-block output blocks,
Bessel RBF with polynomial envelope.  Documented adaptation (DESIGN.md
§4): the angular basis uses cos(l·θ) x Bessel products instead of full
spherical harmonics, and non-molecular graphs (Cora/Reddit/ogbn-
products) synthesise positions from random feature projections with
triplets capped at ``t_max`` per edge.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from . import layers as L


@dataclass(frozen=True)
class DimeNetConfig:
    name: str = "dimenet"
    n_blocks: int = 6
    d_hidden: int = 128
    n_bilinear: int = 8
    n_spherical: int = 7
    n_radial: int = 6
    cutoff: float = 5.0
    envelope_p: int = 6
    n_species: int = 95  # atom-type vocabulary (molecule cells)
    d_feat: int = 0  # >0: project raw features instead of species embed
    n_out: int = 1  # 1 = energy regression; >1 = node classification
    n_graphs: int = 0  # >0: batched-small-graphs (molecule) readout
    # triplet layout: "flat" (T,) index lists (baseline) or "padded"
    # (E, t_max) rows + mask — §Perf iteration B: aligns every triplet
    # with the shard of its target edge, so the interaction needs ONE
    # explicit bf16 all-gather of messages instead of SPMD-inserted f32
    # all-gathers per gather op, and the per-edge aggregation is a local
    # masked row-sum (no segment_sum, no psum).
    triplet_layout: str = "flat"
    t_max: int = 4
    dtype: str = "float32"

    @property
    def n_sbf(self) -> int:
        return self.n_spherical * self.n_radial


def _envelope(d, cutoff, p):
    """DimeNet polynomial envelope u(d) (smooth cutoff)."""
    x = d / cutoff
    a = -(p + 1) * (p + 2) / 2
    b = p * (p + 2)
    c = -p * (p + 1) / 2
    env = 1.0 / jnp.maximum(x, 1e-9) + a * x ** (p - 1) + b * x**p + c * x ** (p + 1)
    return jnp.where(x < 1.0, env, 0.0)


def rbf_basis(d, cfg: DimeNetConfig):
    """Bessel radial basis: (E, n_radial)."""
    n = jnp.arange(1, cfg.n_radial + 1, dtype=jnp.float32)
    env = _envelope(d, cfg.cutoff, cfg.envelope_p)
    return env[:, None] * jnp.sin(n[None, :] * jnp.pi * d[:, None] / cfg.cutoff)


def sbf_basis(d_kj, angle, cfg: DimeNetConfig):
    """Angular x radial basis: (T, n_spherical * n_radial)."""
    n = jnp.arange(1, cfg.n_radial + 1, dtype=jnp.float32)
    l = jnp.arange(cfg.n_spherical, dtype=jnp.float32)
    env = _envelope(d_kj, cfg.cutoff, cfg.envelope_p)
    radial = env[:, None] * jnp.sin(n[None, :] * jnp.pi * d_kj[:, None] / cfg.cutoff)
    angular = jnp.cos(l[None, :] * angle[:, None])  # (T, n_spherical)
    return (angular[:, :, None] * radial[:, None, :]).reshape(d_kj.shape[0], -1)


def _dense(key, i, o, dt):
    return L.dense_init(key, (i, o), dt)


def init(rng, cfg: DimeNetConfig):
    dt = L.dtype_of(cfg.dtype)
    d = cfg.d_hidden
    k = jax.random.split(rng, 8 + cfg.n_blocks)
    params = {
        "embed_z": L.embed_init(k[0], (cfg.n_species, d), dt)
        if cfg.d_feat == 0
        else _dense(k[0], cfg.d_feat, d, dt),
        "emb_rbf": _dense(k[1], cfg.n_radial, d, dt),
        "emb_msg": _dense(k[2], 3 * d, d, dt),
        "out_final": _dense(k[3], d, cfg.n_out, dt),
        "blocks": [],
    }
    for i in range(cfg.n_blocks):
        bk = jax.random.split(k[4 + i], 10)
        params["blocks"].append(
            {
                "w_msg": _dense(bk[0], d, d, dt),
                "w_kj": _dense(bk[1], d, d, dt),
                "w_sbf": _dense(bk[2], cfg.n_sbf, cfg.n_bilinear, dt),
                "w_bil": (
                    jax.random.normal(bk[3], (cfg.n_bilinear, d, d), jnp.float32) * 0.01
                ).astype(dt),
                "w_rbf_g": _dense(bk[4], cfg.n_radial, d, dt),
                "w_up": _dense(bk[5], d, d, dt),
                "w_res1": _dense(bk[6], d, d, dt),
                "w_res2": _dense(bk[7], d, d, dt),
                "w_out_rbf": _dense(bk[8], cfg.n_radial, d, dt),
                "w_out": _dense(bk[9], d, d, dt),
            }
        )
    return params


def synth_positions(feat_or_n, seed: int = 0):
    """Positions for non-molecular graphs: random 3-D projection of
    features (or random coords when only a node count is given)."""
    rng = np.random.default_rng(seed)
    if isinstance(feat_or_n, int):
        return rng.normal(0, 2.0, size=(feat_or_n, 3)).astype(np.float32)
    feat = np.asarray(feat_or_n)
    proj = rng.normal(0, 1.0 / np.sqrt(feat.shape[1]), size=(feat.shape[1], 3))
    return (feat @ proj).astype(np.float32)


def build_triplets_padded(src: np.ndarray, dst: np.ndarray, n_nodes: int, t_max: int = 4):
    """Padded (E, t_max) triplet rows: row ji holds up to t_max incoming
    edges k->j of its source node j (k != i), plus a validity mask."""
    e = len(src)
    order = np.argsort(dst, kind="stable")
    start = np.searchsorted(dst[order], np.arange(n_nodes + 1))
    tri = np.zeros((e, t_max), dtype=np.int32)
    mask = np.zeros((e, t_max), dtype=np.float32)
    for ji in range(e):
        j = src[ji]
        lo, hi = start[j], start[j + 1]
        t = 0
        for p in range(lo, hi):
            if t >= t_max:
                break
            kj = order[p]
            if src[kj] != dst[ji]:
                tri[ji, t] = kj
                mask[ji, t] = 1.0
                t += 1
    return tri, mask


def build_triplets(src: np.ndarray, dst: np.ndarray, n_nodes: int, t_max: int = 4):
    """Triplet index lists (edge_kj -> edge_ji sharing node j), capped at
    ``t_max`` incoming edges per target edge (DESIGN.md §4 adaptation)."""
    e = len(src)
    order = np.argsort(dst, kind="stable")
    by_dst_start = np.searchsorted(dst[order], np.arange(n_nodes + 1))
    tri_kj, tri_ji = [], []
    for ji in range(e):
        j = src[ji]
        lo, hi = by_dst_start[j], by_dst_start[j + 1]
        take = min(t_max, hi - lo)
        for t in range(take):
            kj = order[lo + t]
            if dst[kj] == j and src[kj] != dst[ji]:  # k != i
                tri_kj.append(kj)
                tri_ji.append(ji)
    if not tri_kj:
        tri_kj, tri_ji = [0], [0]
    return np.asarray(tri_kj, dtype=np.int32), np.asarray(tri_ji, dtype=np.int32)


def _edge_axes(ctx):
    ax = ctx.rules.get("edge")
    return tuple(ax) if ax else ()


def _padded_geometry(vec, tri_kj, cfg: DimeNetConfig, ctx):
    """sbf (E_loc rows): one explicit bf16 all-gather of edge vectors,
    then fully local gathers/angles."""
    from jax.sharding import PartitionSpec as P

    axes = _edge_axes(ctx)
    spec = axes if len(axes) > 1 else (axes[0] if axes else None)

    def block(vec_loc, tri_loc):
        vg = vec_loc.astype(jnp.bfloat16)
        if axes:
            vg = lax.all_gather(vg, axes, axis=0, tiled=True)
        v_kj = -jnp.take(vg, tri_loc, axis=0).astype(jnp.float32)  # (E_loc, t, 3)
        v_ji = vec_loc.astype(jnp.float32)[:, None, :]
        cos = jnp.sum(v_ji * v_kj, -1) / (
            jnp.linalg.norm(v_ji, axis=-1) * jnp.linalg.norm(v_kj, axis=-1) + 1e-9
        )
        ang = jnp.arccos(jnp.clip(cos, -1.0, 1.0))  # (E_loc, t)
        d_kj = jnp.linalg.norm(v_kj, axis=-1)
        e, t = ang.shape
        return sbf_basis(d_kj.reshape(-1), ang.reshape(-1), cfg).reshape(e, t, -1)

    if not axes:
        return block(vec, tri_kj)
    return jax.shard_map(
        block,
        mesh=ctx.mesh,
        in_specs=(P(spec, None), P(spec, None)),
        out_specs=P(spec, None, None),
        check_vma=False,
    )(vec, tri_kj)


def _padded_interaction(m, sbf, tri_kj, blk, cfg: DimeNetConfig, ctx):
    """Per-edge triplet aggregation: ONE bf16 all-gather of messages,
    local gathers, masked row-sum — no segment_sum, no psum."""
    from jax.sharding import PartitionSpec as P

    dt = m.dtype
    axes = _edge_axes(ctx)
    spec = axes if len(axes) > 1 else (axes[0] if axes else None)
    w_kj = blk["w_kj"].astype(dt)
    w_sbf = blk["w_sbf"].astype(dt)
    w_bil = blk["w_bil"].astype(dt)

    def block(m_loc, sbf_loc, tri_loc):
        mg = m_loc.astype(jnp.bfloat16)
        if axes:
            mg = lax.all_gather(mg, axes, axis=0, tiled=True)  # (E, d) bf16
        x_kj = jax.nn.silu(jnp.take(mg, tri_loc, axis=0).astype(dt) @ w_kj)  # (E_loc,t,d)
        a = sbf_loc @ w_sbf  # (E_loc, t, n_bil)
        tri = jnp.einsum("etb,bdf,etf->etd", a, w_bil, x_kj)
        return jnp.sum(tri, axis=1)  # masked via sbf's tri_mask factor

    if not axes:
        return block(m, sbf, tri_kj)
    return jax.shard_map(
        block,
        mesh=ctx.mesh,
        in_specs=(P(spec, None), P(spec, None, None), P(spec, None)),
        out_specs=P(spec, None),
        check_vma=False,
    )(m, sbf, tri_kj)


def forward(params, batch, cfg: DimeNetConfig, ctx):
    """batch: pos (N,3), z (N,) or feat (N,F), edge_src/dst (E,),
    tri_kj/tri_ji (T,), node_graph (N,) -> (n_graphs|N, n_out)."""
    dt = L.dtype_of(cfg.dtype)
    pos = batch["pos"].astype(dt)
    src = batch["edge_src"]
    dst = batch["edge_dst"]
    n_nodes = pos.shape[0]

    if cfg.d_feat:
        h = batch["feat"].astype(dt) @ params["embed_z"].astype(dt)
    else:
        h = jnp.take(params["embed_z"], batch["z"], axis=0).astype(dt)

    vec = jnp.take(pos, dst, axis=0) - jnp.take(pos, src, axis=0)  # (E,3)
    vec = ctx.constrain(vec, "edge", None)
    dist = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-9)
    rbf = rbf_basis(dist, cfg).astype(dt)  # (E, n_radial)

    padded = cfg.triplet_layout == "padded"
    if padded:
        # geometry via one explicit bf16 all-gather of edge vectors
        sbf = _padded_geometry(vec, batch["tri_kj"], cfg, ctx).astype(dt)
        sbf = sbf * batch["tri_mask"][..., None].astype(dt)  # (E, tmax, n_sbf)
        sbf = ctx.constrain(sbf, "edge", None, None)
    else:
        # angles for triplets k->j->i: between edge_kj and edge_ji
        v_ji = jnp.take(vec, batch["tri_ji"], axis=0)
        v_kj = -jnp.take(vec, batch["tri_kj"], axis=0)
        cosang = jnp.sum(v_ji * v_kj, -1) / (
            jnp.linalg.norm(v_ji, axis=-1) * jnp.linalg.norm(v_kj, axis=-1) + 1e-9
        )
        angle = jnp.arccos(jnp.clip(cosang, -1.0, 1.0))
        d_kj = jnp.take(dist, batch["tri_kj"])
        sbf = sbf_basis(d_kj, angle, cfg).astype(dt)  # (T, n_sbf)
        sbf = ctx.constrain(sbf, "edge", None)

    # embedding block: directed edge messages
    hj = jnp.take(h, src, axis=0)
    hi = jnp.take(h, dst, axis=0)
    m = jax.nn.silu(
        jnp.concatenate([hj, hi, rbf @ params["emb_rbf"].astype(dt)], -1)
        @ params["emb_msg"].astype(dt)
    )  # (E, d)
    if "edge_mask" in batch:  # padded layout: kill pad-edge messages
        m = m * batch["edge_mask"][:, None].astype(dt)
    m = ctx.constrain(m, "edge", None)

    node_out = jnp.zeros((n_nodes, cfg.d_hidden), dt)
    for blk in params["blocks"]:
        if padded:
            agg = _padded_interaction(m, sbf, batch["tri_kj"], blk, cfg, ctx)
        else:
            # triplet interaction with bilinear contraction
            x_kj = jax.nn.silu(jnp.take(m, batch["tri_kj"], axis=0) @ blk["w_kj"].astype(dt))
            a = sbf @ blk["w_sbf"].astype(dt)  # (T, n_bilinear)
            tri = jnp.einsum("tb,bde,te->td", a, blk["w_bil"].astype(dt), x_kj)
            agg = jax.ops.segment_sum(tri, batch["tri_ji"], num_segments=m.shape[0])
        g = rbf @ blk["w_rbf_g"].astype(dt)
        x = jax.nn.silu(m @ blk["w_msg"].astype(dt)) * g + agg @ blk["w_up"].astype(dt)
        x = x + jax.nn.silu(x @ blk["w_res1"].astype(dt)) @ blk["w_res2"].astype(dt)
        m = m + x  # residual edge-message update
        # output block: edges -> nodes
        contrib = (rbf @ blk["w_out_rbf"].astype(dt)) * m
        node_out = node_out + jax.ops.segment_sum(
            contrib, dst, num_segments=n_nodes
        ) @ blk["w_out"].astype(dt)

    out = node_out @ params["out_final"].astype(dt)  # (N, n_out)
    if cfg.n_out == 1 and cfg.n_graphs > 0:  # molecule energy readout
        return jax.ops.segment_sum(
            out[:, 0], batch["node_graph"], num_segments=cfg.n_graphs
        )
    return out


def loss_fn(params, batch, cfg: DimeNetConfig, ctx):
    out = forward(params, batch, cfg, ctx)
    if cfg.n_out == 1:
        err = out.astype(jnp.float32) - batch["target"].astype(jnp.float32)
        return jnp.mean(err * err)
    logits = out.astype(jnp.float32)
    labels = batch["labels"]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    mask = batch.get("label_mask", jnp.ones_like(gold))
    return jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
