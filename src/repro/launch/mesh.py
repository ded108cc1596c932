"""Production mesh construction (see MULTI-POD DRY-RUN spec).

``make_production_mesh`` is a function — importing this module never
touches jax device state.  The single-pod mesh is (16, 16) = 256 chips
('data', 'model'); the multi-pod mesh is (2, 16, 16) = 512 chips with a
leading 'pod' axis (DP/FSDP compose over ('pod', 'data'); collectives
over 'pod' cross the inter-pod links).
"""

from __future__ import annotations

from repro.dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for multi-device CPU tests (XLA_FLAGS device count)."""
    return make_mesh(shape, axes)
