"""The benchmark's graphs have every link of their budget, and the
whole-graph cell's renaming by the seed changes only the names."""
import numpy as np
import pytest

from bench import graphs, loadgen

SPEC = {"pages": 4000, "links": 30000, "dangling_pct": 80.0,
        "alpha_in": 2.1, "alpha_out": 2.7, "seed": 11}


def _keys(n, src, dst):
    return src.astype(np.int64) * n + dst


@pytest.mark.parametrize("seed", [11, 2**31 + 7])
def test_webgraph_keeps_every_link_distinct_and_sorted(seed):
    n, src, dst = graphs.webgraph(dict(SPEC, seed=seed))
    key = _keys(n, src, dst)
    assert len(key) == SPEC["links"]
    assert (np.diff(key) > 0).all()  # sorted, no repeats
    assert not (src == dst).any()
    dangling = np.bincount(src, minlength=n) == 0
    assert dangling.sum() == round(SPEC["dangling_pct"] / 100 * n)
    again = graphs.webgraph(dict(SPEC, seed=seed))
    assert np.array_equal(src, again[1]) and np.array_equal(dst, again[2])


def test_back_button_links_each_dangling_page_back():
    n, src, dst = graphs.webgraph(SPEC)
    n2, s2, d2 = graphs.build(dict(SPEC, back_button=True))
    dangling = np.bincount(src, minlength=n) == 0
    assert n2 == n and len(s2) == len(src) + int(dangling[dst].sum())
    assert np.isin(_keys(n, dst[dangling[dst]], src[dangling[dst]]),
                   _keys(n, s2, d2)).all()


def test_relabel_changes_names_only_and_follows_the_seed():
    n, src, dst = graphs.build(dict(SPEC, back_button=True))

    def renamed(seed):
        return graphs.relabel(n, src, dst,
                              loadgen.rng_for(seed, loadgen.RELABEL))

    _, s1, d1 = renamed(2**31 + 3)
    _, s2, d2 = renamed(2**31 + 3)
    _, s3, _ = renamed(2**31 + 4)
    assert np.array_equal(s1, s2) and np.array_equal(d1, d2)
    assert not np.array_equal(s1, s3)
    assert (np.diff(_keys(n, s1, d1)) > 0).all()
    for a, b in ((src, s1), (dst, d1)):  # the same degrees, renamed
        assert np.array_equal(np.sort(np.bincount(a, minlength=n)),
                              np.sort(np.bincount(b, minlength=n)))
