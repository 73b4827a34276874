"""Graph substrate: generators (hypothesis), partitions, BSR."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.graph import (WebGraphSpec, generate_webgraph, paper_dataset,
                         partition_edges, partition_edges_by_dst_block,
                         to_bsr, to_csr)
from repro.graph.generators import PAPER_TABLE7
from repro.kernels.ops import pad_empty_rows


@given(st.integers(100, 800), st.floats(0.0, 0.95), st.integers(0, 10))
@settings(max_examples=25, deadline=None)
def test_generator_matches_spec(n, dang, seed):
    g = generate_webgraph(WebGraphSpec(n, n * 6, dang, seed=seed))
    assert g.n_nodes == n
    assert abs(g.dangling_fraction() - dang) < 0.12
    assert (g.src != g.dst).all()  # no self loops
    # dedup'ed
    keys = g.src.astype(np.int64) * n + g.dst
    assert len(np.unique(keys)) == g.n_edges


def test_generator_power_law_skew():
    """Top-1% pages hold a disproportionate share of in-links (the skew the
    paper's acceleration exploits)."""
    g = generate_webgraph(WebGraphSpec(5000, 40000, 0.7, seed=1))
    indeg = np.sort(g.indeg())[::-1]
    top1pct = indeg[:50].sum() / max(indeg.sum(), 1)
    assert top1pct > 0.15


def test_paper_dataset_stats():
    g = paper_dataset("wikipedia", scale=0.2)
    pages, links, pct_dp, _ = PAPER_TABLE7["wikipedia"]
    assert abs(g.n_nodes - pages * 0.2) < 5
    assert abs(g.dangling_fraction() * 100 - pct_dp) < 10


@given(st.integers(50, 400), st.integers(1, 16), st.integers(0, 5))
@settings(max_examples=25, deadline=None)
def test_partition_covers_all_edges(n, shards, seed):
    g = generate_webgraph(WebGraphSpec(n, n * 4, 0.3, seed=seed))
    parts = partition_edges(g, shards)
    src = parts["src"][parts["mask"]]
    dst = parts["dst"][parts["mask"]]
    got = set(zip(src.tolist(), dst.tolist()))
    want = set(zip(g.src.tolist(), g.dst.tolist()))
    assert got == want


def test_dst_block_partition_owns_blocks():
    g = generate_webgraph(WebGraphSpec(200, 1500, 0.4, seed=2))
    parts = partition_edges_by_dst_block(g, 4)
    nb = parts["n_block"]
    for s in range(4):
        d = parts["dst"][s][parts["mask"][s]]
        assert ((d // nb) == s).all()


def test_csr_roundtrip():
    g = generate_webgraph(WebGraphSpec(100, 600, 0.3, seed=3))
    csr = to_csr(g)
    assert (csr.degree() == g.outdeg()).all()
    rebuilt = set()
    for i in range(g.n_nodes):
        for c in csr.cols[csr.ptr[i]:csr.ptr[i + 1]]:
            rebuilt.add((i, int(c)))
    assert rebuilt == set(zip(g.src.tolist(), g.dst.tolist()))


def test_bsr_dense_equivalence():
    g = generate_webgraph(WebGraphSpec(150, 900, 0.4, seed=4))
    bsr = to_bsr(g, 32)
    np.testing.assert_array_equal(bsr.to_dense(), g.to_dense())
    padded = pad_empty_rows(bsr)
    np.testing.assert_array_equal(padded.to_dense(), g.to_dense())
    present = np.zeros(padded.n_block_rows, bool)
    present[padded.brow] = True
    assert present.all()

