"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: 16×16 = 256 chips (data × model).
Multi-pod: 2×16×16 = 512 chips; the 'pod' axis is DCN-connected pure DP.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> jax.sharding.Mesh:
    """Arbitrary mesh (tests / elastic rescale). Axes are ``Auto``: the
    callers place data with ``with_sharding_constraint`` and
    ``NamedSharding``, which ``Explicit`` axes (the ``jax.make_mesh``
    default) reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def axis_sizes(mesh: jax.sharding.Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
