"""Useful bytes of a HITS sweep, counted from the problem alone.

A sweep is a = L^T (ch * h), then h' = L (ca * a): two passes over the
edges, each a gather and a scatter-add, with no reuse to speak of, so the
bound is memory bandwidth and not arithmetic. Per pass the least it must
move is each edge's two int32 endpoints, the input vector and its scale
read once, and the output vector written once. Padded, blocked or sharded
layouts, extra columns and repeated edges are not counted, so the share of
the roofline reads the same whatever backend does the work.
"""
from __future__ import annotations

INDEX_BYTES = 4


def sweep_bytes(n_nodes: int, n_edges: int, dtype_bytes: int) -> int:
    """Least bytes one sweep over a graph of this size must move."""
    per_pass = 2 * INDEX_BYTES * int(n_edges) + 3 * int(n_nodes) * dtype_bytes
    return 2 * per_pass


def query_bytes(n_nodes: int, n_edges: int, sweeps: int,
                dtype_bytes: int) -> int:
    """One answered query: its own focused subgraph, its sweeps to
    convergence and the one certificate sweep."""
    return (int(sweeps) + 1) * sweep_bytes(n_nodes, n_edges, dtype_bytes)


def graph_bytes(n_nodes: int, n_edges: int, sweeps: int,
                dtype_bytes: int) -> int:
    """One whole-graph job of ``sweeps`` sweeps."""
    return int(sweeps) * sweep_bytes(n_nodes, n_edges, dtype_bytes)
