"""Production meshes. A FUNCTION (not module-level constant) so importing
this module never touches jax device state."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """A mesh whose axes are all ``Auto`` (sharding propagated by XLA)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires forced host device count)."""
    return make_mesh(shape, axes)
