"""The whole-graph engine's scatter-free segment sum: one shard's edges in
key order, a scan that restarts at each key's first edge, and each page's
last running sum. It must match ``jax.ops.segment_sum`` on the same edges
in the same dtype, and the engine built on it must match a plain numpy
float64 power iteration for any shard count."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import accel_weights, back_button
from repro.core.engine import RankingEngine, _order, _segment_sum
from repro.graph import WebGraphSpec, generate_webgraph

LIMIT = {"float32": 1e-6, "float64": 1e-14}


def _edges(case, rng):
    """``(n, src, dst, w)`` of one shard."""
    if case == "pages_without_edges":  # only the first third are linked
        n, e = 600, 2000
        src = rng.integers(0, n // 3, e)
        dst = rng.integers(0, n // 3, e)
    elif case == "power_law_hub":  # one page holds most edges either way
        n, e = 500, 3000
        src = np.where(rng.random(e) < 0.7, 3, rng.integers(0, n, e))
        dst = np.where(rng.random(e) < 0.8, 7, rng.integers(0, n, e))
    elif case == "all_sentinel":  # partition_edges' padding: 0 -> 0, w 0
        n, e = 50, 64
        return (n, np.zeros(e, np.int32), np.zeros(e, np.int32),
                np.zeros(e))
    elif case == "single_edge":
        return (10, np.array([5], np.int32), np.array([3], np.int32),
                np.array([0.7]))
    w = rng.random(e) * (rng.random(e) < 0.9)  # some edges masked to 0
    return n, src.astype(np.int32), dst.astype(np.int32), w


@pytest.mark.parametrize("pass_", ["authority", "hub"])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("case", ["pages_without_edges", "power_law_hub",
                                  "all_sentinel", "single_edge"])
def test_segment_sum_matches_scatter_add(case, dtype, pass_):
    rng = np.random.default_rng(5)
    n, src, dst, w = _edges(case, rng)
    # the authority pass sums over in-links, the hub pass over out-links
    key, other = (dst, src) if pass_ == "authority" else (src, dst)
    v = jnp.asarray(rng.random(n), dtype)
    key, other, w = jnp.asarray(key), jnp.asarray(other), jnp.asarray(w, dtype)
    out = _segment_sum(v, *_order(key, other, w, n))
    ref = jax.ops.segment_sum(jnp.take(v, other) * w, key, num_segments=n)
    assert out.shape == (n,) and out.dtype == jnp.dtype(dtype)
    out, ref = np.asarray(out, np.float64), np.asarray(ref, np.float64)
    if not ref.any():
        np.testing.assert_array_equal(out, ref)
    else:
        assert np.abs(out - ref).sum() / np.abs(ref).sum() <= LIMIT[dtype]


def _numpy_accel_hits(g, tol):
    """Accelerated HITS by float64 power iteration, the engine's stopping
    rule: a = L^T (ch h), h' = L (ca a) / |.|_1, until |h' - h|_1 <= tol."""
    n, src, dst = g.n_nodes, g.src, g.dst
    ca, ch = accel_weights(g.indeg(), g.outdeg())
    h = np.full(n, 1.0 / n)
    for k in range(1, 10_000):
        a = np.bincount(dst, weights=(ch * h)[src], minlength=n)
        h_new = np.bincount(src, weights=(ca * a)[dst], minlength=n)
        h_new /= np.abs(h_new).sum() + 1e-30
        delta = np.abs(h_new - h).sum()
        h = h_new
        if delta <= tol:
            break
    return a / (np.abs(a).sum() + 1e-30), h, k


@pytest.fixture(scope="module")
def backbutton_graph():
    return back_button(generate_webgraph(WebGraphSpec(400, 3000, 0.6,
                                                      seed=29)))


@pytest.mark.parametrize("n_shards", [1, 4, 8])
def test_engine_matches_numpy_power_iteration(backbutton_graph, n_shards):
    g = backbutton_graph
    a, h, k = _numpy_accel_hits(g, 1e-12)
    r = RankingEngine(g, "accel", n_shards=n_shards).run(tol=1e-12)
    assert r.converged and r.iters == k
    assert np.abs(r.authority - a).sum() <= 1e-12
    assert np.abs(r.hub - h).sum() <= 1e-12
