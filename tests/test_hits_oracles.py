"""Sparse JAX implementations vs dense fp64 numpy oracles (ground truth)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (accel_hits, accel_weights, back_button, pagerank,
                        qi_hits, spearman)
from repro.core.hits import EdgeList, hits_sweep
from repro.core.ref_dense import (accel_hits_dense, pagerank_dense,
                                  qi_hits_dense)
from repro.graph import WebGraphSpec, generate_webgraph

GRAPHS = [
    WebGraphSpec(n_nodes=150, n_edges=900, dangling_frac=0.5, seed=1),
    WebGraphSpec(n_nodes=300, n_edges=2500, dangling_frac=0.8, seed=2),
    WebGraphSpec(n_nodes=200, n_edges=600, dangling_frac=0.0, seed=3),
]


@pytest.mark.parametrize("spec", GRAPHS, ids=lambda s: f"seed{s.seed}")
def test_qi_hits_matches_dense(spec):
    g = generate_webgraph(spec)
    a_d, h_d, k_d, _ = qi_hits_dense(g, tol=1e-12)
    r = qi_hits(g, tol=1e-12)
    assert r.iters == k_d
    np.testing.assert_allclose(r.aux, a_d, atol=1e-12)
    np.testing.assert_allclose(r.v, h_d, atol=1e-12)


@pytest.mark.parametrize("spec", GRAPHS, ids=lambda s: f"seed{s.seed}")
def test_accel_hits_matches_dense(spec):
    g = generate_webgraph(spec)
    a_d, h_d, k_d, _ = accel_hits_dense(g, tol=1e-12)
    r = accel_hits(g, tol=1e-12)
    assert r.iters == k_d
    np.testing.assert_allclose(r.aux, a_d, atol=1e-12)
    np.testing.assert_allclose(r.v, h_d, atol=1e-12)


@pytest.mark.parametrize("spec", GRAPHS, ids=lambda s: f"seed{s.seed}")
def test_pagerank_matches_dense(spec):
    g = generate_webgraph(spec)
    p_d, k_d, _ = pagerank_dense(g, tol=1e-12)
    r = pagerank(g, tol=1e-12)
    assert r.iters == k_d
    np.testing.assert_allclose(r.v, p_d, atol=1e-12)
    # PageRank vector stays ~stochastic
    assert np.isclose(r.v.sum(), 1.0, atol=1e-8)


def test_back_button_definition():
    """L* = L + M: every edge u->v with v dangling adds v->u."""
    g = generate_webgraph(GRAPHS[0])
    bb = back_button(g)
    dang = g.dangling_mask()
    edges = set(zip(g.src.tolist(), g.dst.tolist()))
    bb_edges = set(zip(bb.src.tolist(), bb.dst.tolist()))
    for (u, v) in edges:
        assert (u, v) in bb_edges
        if dang[v]:
            assert (v, u) in bb_edges
    # no other edges appear
    expected = edges | {(v, u) for (u, v) in edges if dang[v]}
    assert bb_edges == expected
    assert bb.dangling_fraction() < g.dangling_fraction()


def test_multivector_iteration_consistent():
    """V-column batched iteration == V separate runs (same start)."""
    g = generate_webgraph(GRAPHS[0])
    r1 = accel_hits(g, tol=1e-12, v=1)
    r4 = accel_hits(g, tol=1e-12, v=4)
    for j in range(4):
        np.testing.assert_allclose(r4.v[:, j], r1.v, atol=1e-10)


def test_bf16_power_iteration_preserves_ranking():
    """bf16-storage sweeps (the ranking +bf16 mode numerics) keep ordering."""
    g = generate_webgraph(WebGraphSpec(400, 3000, 0.6, seed=21))
    exact = accel_hits(g, tol=1e-11)
    ca, ch = accel_weights(g.indeg(), g.outdeg())
    sweep = jax.jit(hits_sweep(EdgeList.from_graph(g),
                               ca=jnp.asarray(ca, jnp.float32),
                               ch=jnp.asarray(ch, jnp.float32)))
    h = jnp.full((g.n_nodes,), 1.0 / g.n_nodes, jnp.bfloat16)
    for _ in range(60):
        h, _ = sweep(h.astype(jnp.float32))
        h = h.astype(jnp.bfloat16)  # storage dtype between sweeps
    assert spearman(np.asarray(h, np.float64), exact.v) > 0.98
