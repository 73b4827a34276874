"""End-to-end behaviour tests: the paper's full pipeline (crawl-like graph ->
accelerated ranking) and the Pallas-kernel path."""
import jax.numpy as jnp
import numpy as np

from repro.core import (accel_hits, back_button, cosine, qi_hits,
                        topk_overlap)
from repro.core.engine import RankingEngine
from repro.graph import paper_dataset


def test_end_to_end_ranking_pipeline():
    """Synthetic crawl -> back-button -> accelerated HITS -> same ranking as
    exact QI-HITS on the same graph, in far fewer sweeps."""
    g = paper_dataset("wikipedia", scale=0.1)
    bb = back_button(g)
    exact = qi_hits(bb, tol=1e-10)
    fast = accel_hits(bb, tol=1e-10)
    assert fast.iters < exact.iters
    assert cosine(fast.aux, exact.aux) > 0.55
    assert topk_overlap(fast.aux, exact.aux, 20) >= 0.5


def test_end_to_end_engine_with_kernel_path():
    """RankingEngine result == Pallas BSR kernel-path fixed point."""
    from repro.core import accel_weights
    from repro.kernels import hits_sweep_bsr
    g = paper_dataset("jobs", scale=0.05)
    eng = RankingEngine(g, "accel", n_shards=4)
    r = eng.run(tol=1e-11)
    ca, ch = accel_weights(g.indeg(), g.outdeg())
    sweep, _, _ = hits_sweep_bsr(g, ca, ch, bs=128)
    h = jnp.full((g.n_nodes,), 1.0 / g.n_nodes, jnp.float32)
    for _ in range(r.iters + 5):
        h, _ = sweep(h)
    assert np.abs(np.asarray(h, np.float64) - r.hub).max() < 1e-4


def test_power_method_jit_matches_host_loop():
    from repro.core.hits import EdgeList, hits_sweep
    from repro.core.power import power_method, power_method_jit
    g = paper_dataset("opera", scale=0.03)
    edges = EdgeList.from_graph(g)
    sweep = hits_sweep(edges)
    h0 = jnp.full((g.n_nodes,), 1.0 / g.n_nodes, jnp.float64)
    host = power_method(sweep, h0, tol=1e-11)
    v, aux, iters, delta = power_method_jit(sweep, h0, tol=1e-11,
                                            max_iter=2000, check_every=4)
    assert float(delta) <= 1e-11
    np.testing.assert_allclose(np.asarray(v), host.v, atol=1e-9)
