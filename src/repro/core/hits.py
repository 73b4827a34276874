"""QI-HITS (Algorithm 1) and the paper's accelerated HITS (Algorithm 2).

Both are expressed as sweeps over a device-resident edge list and run under
the shared power engine. Vectors may be multi-column (N, V) — V independent
ranking vectors per traversal (personalized/topic HITS).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.structure import Graph
from ..sparse.spmv import normalize_l1, spmv_dst, spmv_src
from .power import PowerResult, power_method
from .weights import accel_weights


@dataclasses.dataclass(frozen=True)
class EdgeList:
    """Device edge list. ``w`` is an optional per-edge weight."""

    src: jnp.ndarray
    dst: jnp.ndarray
    n: int
    w: Optional[jnp.ndarray] = None

    @staticmethod
    def from_graph(g: Graph, dtype=jnp.float32) -> "EdgeList":
        return EdgeList(jnp.asarray(g.src), jnp.asarray(g.dst), g.n_nodes)


def uniform_start(n: int, v: int = 1, dtype=jnp.float64) -> jnp.ndarray:
    x = jnp.full((n, v) if v > 1 else (n,), 1.0 / n, dtype=dtype)
    return x


def hits_sweep(edges: EdgeList, ca=None, ch=None, zeta: float = 1.0):
    """Build the sweep h -> (h_next_normalized, a).

    ca/ch None => Algorithm 1 (QI-HITS); arrays => Algorithm 2.
    zeta < 1 applies the §3.4 primitivity fix on the hub chain:
      sweep(v) := zeta * (v·M) + (1-zeta)/N * sum(v) * e
    applied to both half-steps' combined operator (the one-matrix form of
    the hub matrix), keeping the fixed point unique and positive.
    """

    def sweep(h):
        hw = h if ch is None else h * (ch[:, None] if h.ndim == 2 else ch)
        a = spmv_dst(hw, edges.src, edges.dst, edges.n, edges.w)
        if zeta < 1.0:  # §3.4: smooth both half-steps (X̂ = ζX + (1-ζ)/N eeᵀ)
            a = zeta * a + (1.0 - zeta) / edges.n * jnp.sum(h, axis=0)
        aw = a if ca is None else a * (ca[:, None] if a.ndim == 2 else ca)
        h_new = spmv_src(aw, edges.src, edges.dst, edges.n, edges.w)
        if zeta < 1.0:
            h_new = zeta * h_new + (1.0 - zeta) / edges.n * jnp.sum(a, axis=0)
        h_new = normalize_l1(h_new, axis=0)
        return h_new, a

    return sweep


def _finalize(edges: EdgeList, res: PowerResult, ca=None, ch=None,
              zeta: float = 1.0):
    """Recompute a from the converged h and L1-normalize both."""
    h = jnp.asarray(res.v)
    hw = h if ch is None else h * (ch[:, None] if h.ndim == 2 else ch)
    a = spmv_dst(hw, edges.src, edges.dst, edges.n, edges.w)
    if zeta < 1.0:
        a = zeta * a + (1.0 - zeta) / edges.n * jnp.sum(h, axis=0)
    a = normalize_l1(a, axis=0)
    res.aux = np.asarray(a)
    return res


def qi_hits(g: Graph, tol=1e-10, max_iter=2000, v=1, dtype=jnp.float64,
            zeta: float = 1.0, **kw) -> PowerResult:
    """Algorithm 1. Primary vector = hub, aux = authority."""
    edges = EdgeList.from_graph(g)
    h0 = uniform_start(g.n_nodes, v, dtype)
    res = power_method(hits_sweep(edges, zeta=zeta), h0, tol, max_iter, **kw)
    return _finalize(edges, res, zeta=zeta)


def accel_hits(g: Graph, tol=1e-10, max_iter=2000, v=1, dtype=jnp.float64,
               zeta: float = 1.0, **kw) -> PowerResult:
    """Algorithm 2 — the paper's proposed algorithm."""
    ca_np, ch_np = accel_weights(g.indeg(), g.outdeg())
    ca = jnp.asarray(ca_np, dtype)
    ch = jnp.asarray(ch_np, dtype)
    edges = EdgeList.from_graph(g)
    h0 = uniform_start(g.n_nodes, v, dtype)
    res = power_method(hits_sweep(edges, ca=ca, ch=ch, zeta=zeta), h0,
                       tol, max_iter, **kw)
    return _finalize(edges, res, ca=ca, ch=ch, zeta=zeta)


def column_sweep(authority, hub, ca, ch, mask):
    """The masked multi-query sweep over two passes: ``authority(x)`` sums
    x over each page's in-links, ``hub(x)`` over its out-links, both (N, V)
    -> (N, V). ``hits_sweep_cols`` is it over an edge list's scatter-adds;
    the serve path's dense backend runs it over sorted segment sums
    (``sparse.segsum``)."""

    def sweep(h):
        with jax.named_scope("hits.authority"):
            a = authority(h * ch) * mask
        with jax.named_scope("hits.hub"):
            h_new = hub(a * ca) * mask
        return normalize_l1(h_new, axis=0), a

    return sweep


def hits_sweep_cols(edges: EdgeList, ca, ch, mask):
    """Multi-query sweep: ca/ch/mask are (N, V); column j is accelerated
    HITS restricted to its own focused node set.

    ``mask[:, j]`` is the {0,1} membership of column j's base set S_j; the
    per-column weights must be computed from the degrees *induced by S_j*
    (so they are already zero off-support). Masking each half-step's output
    then removes scatter into off-support nodes, making the column operator
    exactly P_j·L·P_j — the induced subgraph of S_j. One edge traversal
    therefore serves V independent query-focused rankings (the (N, V)
    multi-vector path, driven per-query).
    """
    return column_sweep(
        lambda x: spmv_dst(x, edges.src, edges.dst, edges.n, edges.w),
        lambda x: spmv_src(x, edges.src, edges.dst, edges.n, edges.w),
        ca, ch, mask)


def authority_sweep(edges: EdgeList, ca=None, ch=None, zeta: float = 1.0):
    """One-matrix form (eq. 6): a -> a·X, X = Ca·Lᵀ·Ch·L (ca/ch None = LᵀL).

    Used by the convergence-analysis tests and the extrapolated variants.
    """

    def sweep(a):
        aw = a if ca is None else a * (ca[:, None] if a.ndim == 2 else ca)
        t = spmv_src(aw, edges.src, edges.dst, edges.n, edges.w)
        tw = t if ch is None else t * (ch[:, None] if t.ndim == 2 else ch)
        a_new = spmv_dst(tw, edges.src, edges.dst, edges.n, edges.w)
        if zeta < 1.0:
            tot = jnp.sum(a, axis=0)
            a_new = zeta * a_new + (1.0 - zeta) / edges.n * tot
        return normalize_l1(a_new, axis=0), t

    return sweep
