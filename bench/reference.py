"""Plain numpy float64 reference for both configurations.

It imports nothing of the program and takes nothing the program made:
only the graph's edge arrays and each request's root set.

- ``Index`` is Kleinberg's base-set expansion (1999, section 2): a root
  set, plus at most ``out_cap`` pages each root links to and at most
  ``in_cap`` pages linking to each root. Where a page has more, the ones
  with the smallest ids are taken.
- ``accel_hits`` is the paper's accelerated HITS (eq. 2-3 weights) by
  power iteration from the uniform hub vector, until the hub vector moves
  at most ``tol`` in L1. It returns the authority of the last sweep and
  the hub, both L1-normalised, and the sweep count.
"""
from __future__ import annotations

import numpy as np

# the reference iterates until its hub moves less than this (L1)
REF_TOL = 1e-13


def accel_weights(indeg: np.ndarray, outdeg: np.ndarray):
    """(ca, ch) of the paper's eq. 2-3; pages with no links get 0."""
    indeg = np.asarray(indeg, np.float64)
    outdeg = np.asarray(outdeg, np.float64)
    deg = indeg + outdeg
    live = deg > 0
    ca, ch = np.zeros(len(deg)), np.zeros(len(deg))
    ca[live] = indeg[live] / deg[live]
    ch[live] = outdeg[live] / deg[live]
    imbalance = np.abs(indeg - outdeg)
    more_in, more_out = indeg > outdeg, indeg < outdeg
    ca[more_in] *= imbalance[more_in]
    ch[more_in] /= imbalance[more_in]
    ca[more_out] /= imbalance[more_out]
    ch[more_out] *= imbalance[more_out]
    return ca, ch


def accel_hits(n: int, src, dst, tol: float = REF_TOL,
               max_iter: int = 100_000):
    """Accelerated HITS over one graph's edges; see the module docstring."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    ca, ch = accel_weights(np.bincount(dst, minlength=n),
                           np.bincount(src, minlength=n))
    h = np.full(n, 1.0 / max(n, 1))
    a = np.zeros(n)
    k = 0
    for k in range(1, max_iter + 1):
        # (bincount of no edges is int: cast, for an edgeless base set)
        a = np.bincount(dst, weights=(h * ch)[src], minlength=n) * 1.0
        h_new = np.bincount(src, weights=(a * ca)[dst], minlength=n) * 1.0
        h_new /= np.abs(h_new).sum() + 1e-300
        moved = np.abs(h_new - h).sum()
        h = h_new
        if moved <= tol:
            break
    return a / (np.abs(a).sum() + 1e-300), h, k


class Index:
    """The graph as the reference reads it: out- and in-neighbour lists
    sorted by id, for base-set expansion and induced subgraphs."""

    def __init__(self, n: int, src, dst):
        self.n = int(n)
        self.src = np.asarray(src, np.int64)
        self.dst = np.asarray(dst, np.int64)
        self._out = self._lists(self.src, self.dst)
        self._in = self._lists(self.dst, self.src)

    def _lists(self, key, val):
        order = np.lexsort((val, key))
        ptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(np.bincount(key, minlength=self.n), out=ptr[1:])
        return ptr, val[order]

    @staticmethod
    def _first(lists, roots, cap):
        ptr, vals = lists
        return [vals[ptr[r]:min(ptr[r + 1], ptr[r] + cap)] for r in roots]

    def base_set(self, roots, out_cap: int, in_cap: int) -> np.ndarray:
        """Sorted base set of a root set (see the module docstring)."""
        roots = np.unique(np.asarray(roots, np.int64))
        parts = [roots] + self._first(self._out, roots, out_cap) \
            + self._first(self._in, roots, in_cap)
        return np.unique(np.concatenate(parts))

    def induced(self, nodes: np.ndarray):
        """Edges with both ends in sorted ``nodes``, in local ids."""
        member = np.zeros(self.n, bool)
        member[nodes] = True
        keep = member[self.src] & member[self.dst]
        return (np.searchsorted(nodes, self.src[keep]),
                np.searchsorted(nodes, self.dst[keep]))

    def induced_count(self, nodes: np.ndarray) -> int:
        """How many edges have both ends in ``nodes`` (``induced``'s count,
        read from the out-lists of ``nodes`` alone)."""
        member = np.zeros(self.n, bool)
        member[nodes] = True
        ptr, vals = self._out
        starts, lens = ptr[nodes], ptr[nodes + 1] - ptr[nodes]
        at = np.repeat(starts - np.cumsum(lens) + lens, lens) \
            + np.arange(lens.sum())
        return int(member[vals[at]].sum())

    def rank_query(self, roots, out_cap: int, in_cap: int,
                   tol: float = REF_TOL):
        """(base set, authority, hub): the reference's answer."""
        nodes = self.base_set(roots, out_cap, in_cap)
        s, d = self.induced(nodes)
        a, h, _k = accel_hits(len(nodes), s, d, tol)
        return nodes, a, h
