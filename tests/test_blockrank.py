"""BlockRank-style warm start (paper §2)."""
import numpy as np

from repro.core import accel_hits, qi_hits
from repro.core.blockrank import block_warm_start, hits_blockrank, host_blocks


def _blocky_graph(seed=0):
    """Graph with strong intra-block structure (the BlockRank premise)."""
    rng = np.random.default_rng(seed)
    n, n_hosts = 600, 12
    blocks = host_blocks(n, n_hosts, seed=seed)
    src, dst = [], []
    for _ in range(6000):
        u = rng.integers(0, n)
        if rng.random() < 0.97:  # intra-host link
            same = np.nonzero(blocks == blocks[u])[0]
            v = same[rng.integers(0, len(same))]
        else:
            v = rng.integers(0, n)
        if u != v:
            src.append(u)
            dst.append(v)
    from repro.graph import Graph
    return Graph(n, np.array(src, np.int32), np.array(dst, np.int32)).dedup(), blocks


def test_blockrank_warm_start_reduces_sweeps():
    g, blocks = _blocky_graph()
    cold = accel_hits(g, tol=1e-10)
    warm = hits_blockrank(g, blocks, accelerate=True, tol=1e-10)
    assert warm.converged
    assert warm.iters <= cold.iters
    np.testing.assert_allclose(warm.v, cold.v, atol=1e-8)


def test_blockrank_exactness_plain_hits():
    g, blocks = _blocky_graph(seed=3)
    cold = qi_hits(g, tol=1e-10)
    warm = hits_blockrank(g, blocks, accelerate=False, tol=1e-10)
    np.testing.assert_allclose(warm.v, cold.v, atol=1e-8)


def test_block_warm_start_is_distribution():
    g, blocks = _blocky_graph(seed=5)
    h0 = block_warm_start(g, blocks)
    assert np.isclose(h0.sum(), 1.0)
    assert (h0 >= 0).all()

