"""Logical-axis sharding contexts.

Model and launch code talk in *logical* axes — ``dp`` (data parallel),
``fsdp`` (parameter shards), ``tp`` (tensor parallel), ``ep`` (expert
parallel), ``edge`` (GNN edge shards), ``row`` (embedding-table rows) —
and a :class:`ShardingCtx` resolves them onto the physical mesh axes the
launcher built (``('data', 'model')`` single-pod, ``('pod', 'data',
'model')`` multi-pod; see :mod:`repro.launch.mesh`).

Two profiles cover the repo's architectures:

* ``tp_fsdp`` (LMs): dp/fsdp over the data-like axes, tp/ep over
  ``model``.
* ``flat_dp`` (recsys / GNN): every logical data axis flattens over the
  whole mesh; tp/ep are unused.

``edge`` and ``row`` always span the full mesh — both are "shard the big
flat thing over everything" axes.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PROFILES = ("tp_fsdp", "flat_dp")

# logical name -> which mesh axes (by preference) it may occupy
_DATA_AXES = ("pod", "data")
_MODEL_AXES = ("model",)


def _rules_for(profile: str, mesh_axes: tuple) -> dict:
    present = tuple(a for a in mesh_axes)
    data = tuple(a for a in _DATA_AXES if a in present)
    model = tuple(a for a in _MODEL_AXES if a in present)
    if profile == "tp_fsdp":
        rules = {"dp": data, "fsdp": data, "tp": model, "ep": model}
    elif profile == "flat_dp":
        rules = {"dp": present, "fsdp": present, "tp": (), "ep": ()}
    else:
        raise ValueError(f"unknown sharding profile {profile!r}; choose from {PROFILES}")
    rules["edge"] = present
    rules["row"] = present
    return rules


@dataclass
class ShardingCtx:
    """Resolves logical axis names against a concrete mesh.

    ``rules`` maps each logical name to a (possibly empty) tuple of mesh
    axis names; model code may read it directly (e.g. for shard_map
    in_specs) or go through :meth:`sharding` / :meth:`constrain`.
    """

    mesh: Mesh
    profile: str = "tp_fsdp"
    rules: dict = field(default_factory=dict)

    def __post_init__(self):
        if not self.rules:
            self.rules = _rules_for(self.profile, tuple(self.mesh.axis_names))
        # normalise user-supplied rules: a bare string ("model") is one
        # mesh axis, not an iterable of single-character axis names
        self.rules = {
            k: ((v,) if isinstance(v, str) else tuple(v or ())) for k, v in self.rules.items()
        }

    # -- resolution -------------------------------------------------------
    def _resolve(self, logical):
        if logical is None:
            return None
        if isinstance(logical, tuple):  # already-flat tuple of logical names
            axes = []
            for l in logical:
                r = self._resolve(l)
                if r is None:
                    continue
                axes.extend(r if isinstance(r, tuple) else (r,))
            if not axes:
                return None
            return axes[0] if len(axes) == 1 else tuple(axes)
        ax = self.rules.get(logical, ())
        if not ax:
            return None
        return ax[0] if len(ax) == 1 else tuple(ax)

    def spec(self, *logical) -> P:
        return P(*[self._resolve(l) for l in logical])

    def sharding(self, *logical) -> NamedSharding:
        """NamedSharding for a value whose dims carry these logical axes."""
        return NamedSharding(self.mesh, self.spec(*logical))

    def constrain(self, x, *logical):
        """with_sharding_constraint, a no-op on a single-device mesh."""
        if self.mesh.size == 1:
            return x
        return jax.lax.with_sharding_constraint(x, self.sharding(*logical))

    def mesh_axes(self, logical: str) -> tuple:
        """Mesh axis names a logical axis resolves to (possibly empty).

        shard_map callers need the *physical* axis names for collectives
        (``lax.all_to_all``/``psum`` take mesh axes, not logical ones).
        """
        return tuple(self.rules.get(logical, ()))

    def n(self, logical: str) -> int:
        """Number of shards a logical axis resolves to (1 if unmapped).

        Returns the resolved product over *all* mesh axes the logical
        axis occupies — size-1-padded axes multiply in as 1 rather than
        being dropped — and refuses to silently treat a rule that names
        a mesh axis absent from this mesh as unmapped.
        """
        out = 1
        for a in self.mesh_axes(logical):
            if a not in self.mesh.shape:
                raise ValueError(
                    f"logical axis {logical!r} resolves to mesh axis {a!r}, "
                    f"which is not on this mesh (axes: {tuple(self.mesh.axis_names)})"
                )
            out *= int(self.mesh.shape[a])
        return out


def make_mesh(shape: tuple, axes: tuple, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    Since JAX 0.8 ``jax.make_mesh`` defaults to ``Explicit`` axes, under
    which ``with_sharding_constraint`` and the sharded contractions of
    the model code are refused.  Every mesh of this repo is built here.
    """
    axis_types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=axis_types, devices=devices)


def single_device_ctx(profile: str = "tp_fsdp") -> ShardingCtx:
    """A (1, 1) ``('data', 'model')`` mesh on the first local device."""
    return ShardingCtx(mesh=make_mesh((1, 1), ("data", "model")), profile=profile)
