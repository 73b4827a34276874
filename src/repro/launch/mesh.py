"""The device mesh builder. A function (not a module constant) so importing
never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axes (its default is Explicit)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))

