"""The serve path's scatter-free column segment sum (``sparse.segsum``):
edges sorted by key once, laid out for V columns once a batch, then a
gather of whole rows, a scan that restarts at each key's first edge and
each page's last running sum. It must match ``jax.ops.segment_sum`` on the
same edges, and the dense backend's loop built on it must match the
scatter-add sweep (``core.hits.hits_sweep_cols``) it replaced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hits import EdgeList, hits_sweep_cols
from repro.core.weights import accel_weights
from repro.serve.backends import _converge_batch
from repro.sparse.segsum import segment_sum_cols, sort_edges

# relative L1 to float64 sums of the same (rounded) inputs, from the dtype
LIMIT = {"float64": 1e-14, "float32": 1e-6, "bfloat16": 3e-2}


def _edges(case, rng):
    """``(n, key, other, w)``: one pass's edge list."""
    if case == "pages_without_edges":  # only the first third are keyed
        n, e = 600, 2000
        key = np.sort(rng.integers(0, n // 3, e))
    elif case == "padding_tail":  # the assembled union's: n-1 -> n-1, w 0
        n, e, real = 256, 1024, 700
        key = np.concatenate([np.sort(rng.integers(0, n - 1, real)),
                              np.full(e - real, n - 1)])
        other = np.concatenate([rng.integers(0, n - 1, real),
                                np.full(e - real, n - 1)])
        w = np.concatenate([rng.random(real), np.zeros(e - real)])
        return n, key.astype(np.int32), other.astype(np.int32), w
    elif case == "single_edge":
        return (10, np.array([3], np.int32), np.array([5], np.int32),
                np.array([0.7]))
    elif case == "ragged_length":  # no multiple of any chunk count
        n, e = 300, 1237
        key = np.sort(rng.integers(0, n, e))
    elif case == "unsorted_keys":  # one page holds most edges
        n, e = 500, 3000
        key = np.where(rng.random(e) < 0.6, 7, rng.integers(0, n, e))
    other = rng.integers(0, n, e)
    w = rng.random(e) * (rng.random(e) < 0.9)  # some edges masked to 0
    return n, key.astype(np.int32), other.astype(np.int32), w


@pytest.mark.parametrize("v", [1, 8])
@pytest.mark.parametrize("case,dtype", [
    ("pages_without_edges", "float64"), ("padding_tail", "float64"),
    ("single_edge", "float64"), ("ragged_length", "float64"),
    ("unsorted_keys", "float64"), ("unsorted_keys", "float32"),
    ("unsorted_keys", "bfloat16")])
def test_segment_sum_cols_matches_segment_sum(case, dtype, v):
    rng = np.random.default_rng(11)
    n, key, other, w = _edges(case, rng)
    x = jnp.asarray(rng.random((n, v)), dtype)
    w = jnp.asarray(w, dtype)
    edges = sort_edges(jnp.asarray(key), jnp.asarray(other), w, n)
    out = jax.jit(segment_sum_cols)(x, edges)
    assert out.shape == (n, v) and out.dtype == jnp.dtype(dtype)
    x64, w64 = (np.asarray(a, np.float64) for a in (x, w))
    ref = np.asarray(jax.ops.segment_sum(x64[other] * w64[:, None], key,
                                         num_segments=n))
    out = np.asarray(out, np.float64)
    np.testing.assert_array_equal(out[~ref.any(axis=1)], 0)
    assert np.abs(out - ref).sum() <= LIMIT[dtype] * np.abs(ref).sum()


def _union(rng, n_pad=512, e_pad=4096, real=3000, v=4):
    """An assembled-style batch: real edges sorted by source, padding to
    the dead row n_pad-1 with weight 0, and V columns of induced weights
    and masks."""
    n = n_pad - 1
    src = np.sort(rng.integers(0, n, real))
    dst = (n * rng.random(real) ** 2).astype(np.int64)
    pad = np.full(e_pad - real, n_pad - 1)
    src, dst = np.concatenate([src, pad]), np.concatenate([dst, pad])
    w = np.concatenate([np.ones(real), np.zeros(e_pad - real)])
    mask = np.zeros((n_pad, v))
    ca, ch = np.zeros((n_pad, v)), np.zeros((n_pad, v))
    for j in range(v):
        m = np.zeros(n_pad, bool)
        m[rng.choice(n, n // (j + 2), replace=False)] = True
        sel = m[src] & m[dst] & (w > 0)
        ca[:, j], ch[:, j] = accel_weights(
            np.bincount(dst[sel], minlength=n_pad),
            np.bincount(src[sel], minlength=n_pad))
        ca[:, j] *= m
        ch[:, j] *= m
        mask[:, j] = m
    h0 = mask / mask.sum(axis=0)
    return (src.astype(np.int32), dst.astype(np.int32), w, h0, ca, ch,
            mask)


@pytest.mark.parametrize("bulk_dtype", [None, "float32"])
def test_converge_batch_matches_scatter_add_sweep(bulk_dtype):
    """The dense loop over sorted segment sums against the scatter-add
    sweep it replaced, run by the same stopping rule: the same sweep
    counts, the same fixed point and a certificate at the same level."""
    rng = np.random.default_rng(3)
    src, dst, w, h0, ca, ch, mask = _union(rng)
    tol, n_pad = 1e-12, h0.shape[0]
    s, d, wd = jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w)
    h, a, conv, res = _converge_batch(
        jnp.asarray(h0), sort_edges(d, s, wd, n_pad),
        sort_edges(s, d, wd, n_pad),
        jnp.asarray(ca), jnp.asarray(ch), jnp.asarray(mask), tol, 500,
        bulk_dtype=bulk_dtype, bulk_tol=1e-4)
    # the scatter-add sweep, iterated until every column is within tol
    sweep = hits_sweep_cols(EdgeList(s, d, n_pad, wd), jnp.asarray(ca),
                            jnp.asarray(ch), jnp.asarray(mask))
    hr, conv_r = jnp.asarray(h0), np.full(h0.shape[1], -1)
    for k in range(1, 501):
        hn, _ = sweep(hr)
        delta = np.asarray(jnp.sum(jnp.abs(hn - hr), axis=0))
        conv_r = np.where((conv_r < 0) & (delta <= tol), k, conv_r)
        hr = hn
        if (conv_r > 0).all():
            break
    _, ar = sweep(hr)
    ar = ar / jnp.sum(jnp.abs(ar), axis=0)
    if bulk_dtype is None:
        np.testing.assert_array_equal(np.asarray(conv), conv_r)
    assert np.abs(np.asarray(h) - np.asarray(hr)).sum(axis=0).max() <= 1e-10
    assert np.abs(np.asarray(a) - np.asarray(ar)).sum(axis=0).max() <= 1e-10
    assert np.asarray(res).max() <= 10 * tol
