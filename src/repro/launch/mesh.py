"""Production mesh builders. Functions (not module constants) so importing
never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axes (its default is Explicit)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (=256 chips/pod) single-pod, or 2x16x16 (=512 chips) multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = n // model
    return make_mesh((data, model), ("data", "model"))
