"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run must set XLA_FLAGS
before anything initializes the backend.

Production target: TPU v5e pods, 256 chips each, 16x16 (data, model) per
pod; the multi-pod mesh adds a leading ``pod`` axis (2 x 16 x 16 = 512
chips) for cross-pod data parallelism over DCN.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1) -> Mesh:
    """Whatever this host has (tests / examples): (devices/model, model)."""
    n = len(jax.devices())
    assert n % model == 0
    if model > 1:
        return jax.make_mesh((n // model, model), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))


def mesh_chips(mesh: Mesh) -> int:
    return int(mesh.devices.size)
