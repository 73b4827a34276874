"""Sharding hints that degrade gracefully outside a mesh context.

Models annotate activations with logical specs like ``(DP, None, "model")``
where DP = ("pod", "data"). ``shard_hint`` filters axes absent from the
current abstract mesh (single-pod meshes have no "pod"; smoke tests have no
mesh at all), so the same model code runs everywhere.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

DP = ("pod", "data")  # canonical data-parallel axes (outermost first)


def _filter_axis(a, names):
    if a is None:
        return None
    if isinstance(a, (tuple, list)):
        kept = tuple(x for x in a if x in names)
        return kept if len(kept) > 1 else (kept[0] if kept else None)
    return a if a in names else None


def shard_hint(x, *spec):
    """with_sharding_constraint if a mesh is active; identity otherwise."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    names = set(mesh.axis_names)
    clean = tuple(_filter_axis(a, names) for a in spec)
    return jax.lax.with_sharding_constraint(x, P(*clean))


def filter_spec(spec, mesh) -> P:
    """Concretize a logical PartitionSpec against a mesh (drop absent axes)."""
    names = set(mesh.axis_names)
    return P(*tuple(_filter_axis(a, names) for a in spec))


def tree_filter_specs(tree, mesh):
    return jax.tree.map(
        lambda s: filter_spec(s, mesh),
        tree,
        is_leaf=lambda s: isinstance(s, P),
    )
