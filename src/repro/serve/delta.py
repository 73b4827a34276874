"""Edge-delta classification and application for live graph mutation.

``RankService.apply_edge_delta`` takes an operator's changeset — link
adds, removes, reweights, and new pages — and rolls it into a running
service without a restart and without closing admission. This module
owns the graph-side half of that: normalizing and validating the
changeset, classifying it (weight-only vs structural), and producing the
post-delta edge list + edge-weight table. The service-side half (the
version swap, cache invalidation, plan patch-vs-replan, spill generation
bump, warm-table carryover) lives in ``rank_service.py``.

Classification drives how much cached state survives:

* **weight-only** (reweights, no adds/removes/pages) — every union
  subgraph keeps its topology, so every cached plan's *layout* survives;
  backends patch edge-value arrays / BSR block values in place
  (``SweepBackend.patch``, probed lazily at the next plan lookup via the
  weight-blind ``plans.topology_key``).
* **structural** (any add, remove or new page) — the service builds a
  new extractor, but plans are content-keyed: union subgraphs the delta
  doesn't touch produce byte-identical padded edge arrays, so their plans
  (and cached vectors outside the touched node set) keep hitting. Only
  affected plans rebuild.

In both cases the warm table carries over: the paper's premise is that
pre-delta fixed points are excellent warm starts, so post-delta
refreshes converge in a handful of sweeps instead of from uniform.

**New pages.** A delta with ``pages=k`` grows an ``n``-page graph to
``n + k`` pages with ids ``n, n+1, …, n+k-1``; its links may name them.
A page with no links is ranked like any other dangling page.

Weight rules: weights must be finite and nonzero. A reweight to 0 is a
remove (and a zero-weight add is just a remove of nothing) — routing
them through ``removes`` keeps "edge exists" equivalent to "edge has
nonzero weight", which is what lets the BSR patch path trust that a
surviving topology keeps the same retained-edge set. Adding a pair that
already exists is treated as a reweight (idempotent rolls); removing or
reweighting a pair that doesn't exist raises (operator typo, not a
no-op).
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Optional, Tuple

import numpy as np

from ..graph.structure import Graph

# (sorted unique int64 src*n+dst keys, aligned float64 weights): the
# service's edge-weight table. None means "no delta ever applied" — every
# weight is 1.0 and assemble skips the lookup entirely.
EdgeTable = Tuple[np.ndarray, np.ndarray]


def _pairs(spec, n_nodes: int, what: str, with_w: bool,
           require_w: bool = False):
    """Normalize one changeset field to ((k,2) int64 pairs, (k,) f64 w)."""
    if spec is None:
        e = np.zeros((0, 2), np.int64)
        return e, np.zeros(0, np.float64)
    rows = list(spec)
    pairs = np.zeros((len(rows), 2), np.int64)
    w = np.ones(len(rows), np.float64)
    for i, row in enumerate(rows):
        row = tuple(row)
        if len(row) == 2 and not require_w:
            s, d = row
        elif len(row) == 3 and with_w:
            s, d, w[i] = row
        else:
            want = ("(src, dst, w)" if require_w
                    else f"(src, dst{', w' if with_w else ''})")
            raise ValueError(f"{what}[{i}]: want {want}, got {row!r}")
        pairs[i] = (int(s), int(d))
    if len(rows):
        if pairs.min() < 0 or pairs.max() >= n_nodes:
            raise ValueError(f"{what}: node id outside [0, {n_nodes})")
        if with_w and (~np.isfinite(w) | (w == 0)).any():
            raise ValueError(
                f"{what}: weights must be finite and nonzero "
                "(a reweight to 0 is a remove — use removes)")
    return pairs, w


@dataclasses.dataclass(frozen=True)
class EdgeDelta:
    """A normalized changeset against an n_nodes-page graph.

    ``adds``/``removes``/``reweights`` are (k, 2) int64 (src, dst) pair
    arrays; ``add_w``/``rw_w`` the aligned weights; ``pages`` the number
    of new pages, whose ids follow the graph's last. Page ids are already
    range-checked against ``n_nodes + pages``; weights finite and nonzero.
    """

    adds: np.ndarray
    add_w: np.ndarray
    removes: np.ndarray
    reweights: np.ndarray
    rw_w: np.ndarray
    pages: int = 0

    @staticmethod
    def normalize(adds: Optional[Iterable] = None,
                  removes: Optional[Iterable] = None,
                  reweights: Optional[Iterable] = None,
                  n_nodes: int = 0, pages: int = 0) -> "EdgeDelta":
        if int(pages) != pages or pages < 0:
            raise ValueError(f"pages: want a whole number >= 0, got "
                             f"{pages!r}")
        n = n_nodes + int(pages)
        a, aw = _pairs(adds, n, "adds", with_w=True)
        r, _ = _pairs(removes, n, "removes", with_w=False)
        rw, rww = _pairs(reweights, n, "reweights", with_w=True,
                         require_w=True)
        return EdgeDelta(a, aw, r, rw, rww, int(pages))

    @property
    def empty(self) -> bool:
        return not (len(self.adds) or len(self.removes)
                    or len(self.reweights) or self.pages)

    @property
    def structural(self) -> bool:
        """Does the delta change topology (vs edge values only)?"""
        return bool(len(self.adds) or len(self.removes) or self.pages)

    def touched_nodes(self) -> np.ndarray:
        """Sorted unique endpoints of every changed edge — the node set
        whose cached results the service must invalidate (any union
        subgraph containing one of these may rank differently)."""
        return np.unique(np.concatenate(
            [self.adds.ravel(), self.removes.ravel(),
             self.reweights.ravel()]))


def _table_of(g: Graph, table: Optional[EdgeTable], n: int) -> EdgeTable:
    """A copy of the service's weight table keyed over ``n`` pages
    (all-1.0 when no delta has ever run)."""
    if table is None:
        keys = np.unique(g.src.astype(np.int64) * n + g.dst)
        return keys, np.ones(len(keys), np.float64)
    keys, vals = table
    if n != g.n_nodes:  # new pages: re-key (a new array); order is kept
        return keys // g.n_nodes * n + keys % g.n_nodes, vals.copy()
    return keys.copy(), vals.copy()


def _member(keys: np.ndarray, q: np.ndarray) -> Tuple[np.ndarray,
                                                     np.ndarray]:
    """(positions, found) of the keys ``q`` in the sorted ``keys``: a
    binary search each, so a changeset costs O(k log E), not a sort of
    the table."""
    pos = np.minimum(np.searchsorted(keys, q), max(len(keys) - 1, 0))
    found = keys[pos] == q if len(keys) else np.zeros(len(q), bool)
    return pos, found


def apply_to_graph(g: Graph, table: Optional[EdgeTable],
                   delta: EdgeDelta) -> Tuple[Graph, EdgeTable]:
    """The post-delta (graph, edge-weight table) pair.

    Pure: neither input is mutated — the caller swaps both under its own
    lock. Weights are keyed per (src, dst) pair; duplicate edges in the
    underlying graph share their pair's weight, mirroring the unweighted
    behavior where each duplicate contributes 1.0. Raises ValueError on
    removes/reweights of absent pairs and adds handled per the module
    rules above.
    """
    n = g.n_nodes + delta.pages
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    tkeys, tvals = _table_of(g, table, n)

    if len(delta.removes):
        rk = np.unique(delta.removes[:, 0] * n + delta.removes[:, 1])
        pos, found = _member(tkeys, rk)
        if not found.all():
            missing = rk[~found]
            raise ValueError(
                f"removes: {missing.size} pair(s) not in the graph "
                f"(first: ({missing[0] // n}, {missing[0] % n}))")
        keep = ~np.isin(src.astype(np.int64) * n + dst, rk)
        src, dst = src[keep], dst[keep]
        tkeys, tvals = np.delete(tkeys, pos), np.delete(tvals, pos)

    if len(delta.adds):
        ak = delta.adds[:, 0] * n + delta.adds[:, 1]
        # last occurrence wins within one changeset
        ak, last = np.unique(ak[::-1], return_index=True)
        aw = delta.add_w[::-1][last]
        pos, exists = _member(tkeys, ak)
        # adding an existing pair == reweighting it (idempotent rolls)
        tvals[pos[exists]] = aw[exists]
        new_k, new_w = ak[~exists], aw[~exists]
        if new_k.size:
            src = np.concatenate([src, (new_k // n).astype(src.dtype)])
            dst = np.concatenate([dst, (new_k % n).astype(dst.dtype)])
            # new_k is sorted: inserting at its search positions keeps
            # the table sorted
            at = np.searchsorted(tkeys, new_k)
            tkeys = np.insert(tkeys, at, new_k)
            tvals = np.insert(tvals, at, new_w)

    if len(delta.reweights):
        wk = delta.reweights[:, 0] * n + delta.reweights[:, 1]
        pos, found = _member(tkeys, wk)
        if not found.all():
            bad = wk[~found]
            raise ValueError(
                f"reweights: {bad.size} pair(s) not in the graph "
                f"(first: ({bad[0] // n}, {bad[0] % n}))")
        tvals[pos] = delta.rw_w

    return Graph(n, src, dst), (tkeys, tvals)


def lookup_weights(table: Optional[EdgeTable], n_nodes: int,
                   gsrc: np.ndarray, gdst: np.ndarray) -> Optional[np.ndarray]:
    """Per-edge weights for edges given by *global* endpoint arrays, or
    None when no table exists (every weight is 1.0). Every queried edge
    must be in the table — serving only ever looks up edges induced from
    the graph the table was built against."""
    if table is None:
        return None
    keys, vals = table
    gk = gsrc.astype(np.int64) * n_nodes + gdst
    pos = np.searchsorted(keys, gk)
    return vals[pos]
