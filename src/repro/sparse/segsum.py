"""Column segment sums over edges in key order, with no scatter.

``segment_sum_cols(x, edges)[j] = sum(x[other[e]] * w[e] for the edges e
keyed j)`` for an (N, V) block of vectors: the same sum as
``spmv.spmv_dst`` / ``spmv.spmv_src``, without their scatter-add. Emulated
float64 makes a scatter-add the slowest op of a pass on a TPU; here a pass
is a gather of each edge's ``(V,)`` values, a segmented scan down the edges
that restarts at each key's first edge, and a gather of each page's last
running sum.

``sort_edges`` orders an edge list once (the serve path's plan) and puts
it on the device; ``segment_sum_cols`` is one pass. A pass works on (V, E)
blocks: the edges along the lanes, the V columns down the sublanes, so V=8
f64 columns fill whole vregs with no padding. The scan doubles its reach
each step, always measured back from the edge it sums into, so a page's
sum depends only on its own edges' values and order, wherever its run
lies: pages with equal in-link values get equal sums, as a serial sum
gives, and exact ties stay ties.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class SortedEdges(NamedTuple):
    """An edge list in key order, as ``sort_edges`` makes it."""

    other: jax.Array  # (E,) int32: the endpoint a pass reads
    w: jax.Array      # (E,) edge weights
    start: jax.Array  # (E,) bool: the edge opens its key's run
    last: jax.Array   # (N,) int32: each page's last edge (0 without one)
    has: jax.Array    # (N,) bool: the page has an edge here
    perm: jax.Array   # (E,) int32: each sorted edge's index in the input


def sort_edges(key, other, w, n: int) -> SortedEdges:
    """The edges of pages ``0..n-1`` by ``key`` (stable: a page's edges keep
    their input order), put on the device.

    The order is found on the host. A device sort compiles one program per
    edge count, and with the weights carried through it as an f64 operand
    that program takes the TPU compiler about half a minute: a serving
    process meeting a new edge count would stall that long. Keys that are
    already in order (the union's edges by source, as assembly emits them)
    need no sort.
    """
    key, other, w = np.asarray(key), np.asarray(other), np.asarray(w)
    e = key.shape[0]
    if (key[1:] >= key[:-1]).all():
        perm = np.arange(e)
    else:  # a 16-bit key sorts by radix
        perm = np.argsort(key.astype(np.uint16) if n <= 1 << 16 else key,
                          kind="stable")
        key, other, w = key[perm], other[perm], w[perm]
    start = np.ones(e, bool)
    start[1:] = key[1:] != key[:-1]
    pages = np.arange(n)
    # one past each page's last edge; the page has edges iff the edge
    # before that is its own
    end = np.searchsorted(key, pages, side="right")
    last = np.maximum(end - 1, 0)
    has = (end > 0) & (key[last] == pages)
    return SortedEdges(*(jnp.asarray(x) for x in (
        other.astype(np.int32), w, start, last.astype(np.int32), has,
        perm.astype(np.int32))))


@jax.jit
def reweight(edges: SortedEdges, w) -> SortedEdges:
    """``edges`` with new weights ``w``, given in the input order."""
    return edges._replace(w=jnp.take(w, edges.perm))


def _shifted(x, s):
    """``x`` moved ``s`` places along its last axis, zeros shifted in."""
    return jnp.pad(x[..., :-s], [(0, 0)] * (x.ndim - 1) + [(s, 0)])


def _restarting_scan(flag, x):
    """Inclusive sum along the last axis that restarts at each flagged
    place, by doubling: after the step of shift ``s`` each place holds the
    sum of the ``2s`` places ending at it, cut at the last restart among
    them. Each sum is grouped by distances back from its own place."""
    shift = 1
    while shift < x.shape[-1]:
        x = jnp.where(flag, x, x + _shifted(x, shift))
        flag = flag | _shifted(flag, shift)
        shift *= 2
    return x


def segment_sum_cols(x, edges: SortedEdges):
    """``out[j] = sum(x[other[e]] * w[e] for the edges e keyed j)`` for an
    (N, V) ``x``, in the dtype of ``x`` and ``w``: (N, V), 0 where a page
    has no edge."""
    with jax.named_scope("segsum.gather"):
        g = jnp.take(x.T, edges.other, axis=1) * edges.w        # (V, E)
    with jax.named_scope("segsum.scan"):
        run = _restarting_scan(edges.start, g)
    with jax.named_scope("segsum.ends"):
        return jnp.where(edges.has[:, None],
                         jnp.take(run, edges.last, axis=1).T, 0)
