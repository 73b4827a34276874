"""The benchmark's graphs, made from a configuration's numbers alone.

Kept here so that no change to the program can change the data a cell
runs on. It follows the web-graph generator the program ships
(``repro.graph.generators.generate_webgraph``) and its back-button
transform (``repro.core.backbutton.back_button``), except that a drawn
link that repeats another, or points at its own source, is drawn again,
so the graph has every link of its budget. All return plain
``(n, src, dst)`` numpy arrays, edges sorted by (source, destination);
the drivers wrap them in the program's own ``Graph``.
"""
from __future__ import annotations

import numpy as np


def _dedup(n: int, src: np.ndarray, dst: np.ndarray):
    key = src.astype(np.int64) * n + dst
    _, idx = np.unique(key, return_index=True)
    return src[idx], dst[idx]


def _distinct_links(rng, n: int, src: np.ndarray, cdf: np.ndarray):
    """Sorted keys ``src * n + dst`` of one link per entry of ``src``, each
    destination drawn from ``cdf``; a repeat or a self-loop is drawn again
    until every link is distinct."""
    good = np.zeros(0, np.int64)
    need = src.astype(np.int64)
    while need.size:
        dst = np.searchsorted(cdf, rng.random(need.size), side="right")
        key = need * n + dst
        order = np.argsort(key, kind="stable")
        key, need = key[order], need[order]
        ok = need != dst[order]
        ok[1:] &= key[1:] != key[:-1]
        pos = np.searchsorted(good, key)
        inside = pos < good.size
        ok[inside] &= good[pos[inside]] != key[inside]
        good = np.insert(good, pos[ok], key[ok])
        need = need[~ok]
    return good


def webgraph(spec: dict):
    """Directed power-law graph with a controlled dangling share.

    ``spec`` holds ``pages``, ``links``, ``dangling_pct``, ``alpha_in``,
    ``alpha_out`` and the generator's ``seed``. Non-dangling pages get
    out-degrees from a Zipf split of the link budget; destinations are drawn
    by popularity over all pages, distinct for each source and never the
    source itself, so the graph has exactly ``links`` links."""
    n, e = int(spec["pages"]), int(spec["links"])
    rng = np.random.default_rng(int(spec["seed"]))
    n_dangling = int(round(spec["dangling_pct"] / 100.0 * n))
    n_src = max(n - n_dangling, 1)

    perm = rng.permutation(n)
    src_pool = perm[:n_src]
    w_out = rng.zipf(spec["alpha_out"], size=n_src).astype(np.float64)
    w_out = w_out / w_out.sum()
    outdeg = np.maximum(1, np.round(w_out * e)).astype(np.int64)
    excess = int(outdeg.sum() - e)
    order = np.argsort(-outdeg)
    if excess < 0:  # rounding left links over: the largest sources take them
        outdeg[order[: -excess]] += 1
    i = 0
    while excess > 0 and i < len(order):
        take = min(excess, int(outdeg[order[i]]) - 1)
        outdeg[order[i]] -= take
        excess -= take
        i += 1
    if outdeg.max() >= n:
        raise ValueError(f"a source with {outdeg.max()} links among {n} pages")
    src = np.repeat(src_pool, outdeg)

    ranks = rng.permutation(n) + 1
    w_in = ranks.astype(np.float64) ** (-(spec["alpha_in"] - 1.0))
    cdf = np.cumsum(w_in)
    cdf /= cdf[-1]
    key = _distinct_links(rng, n, src, cdf)
    return n, (key // n).astype(np.int32), (key % n).astype(np.int32)


def back_button(n: int, src: np.ndarray, dst: np.ndarray):
    """The paper's back-button graph (section 3.3): for every link u -> v
    with v dangling, add v -> u; duplicates dropped."""
    dangling = np.bincount(src, minlength=n) == 0
    to_dangling = dangling[dst]
    src2 = np.concatenate([src, dst[to_dangling]]).astype(np.int32)
    dst2 = np.concatenate([dst, src[to_dangling]]).astype(np.int32)
    s, d = _dedup(n, src2, dst2)
    return n, s, d


def relabel(n: int, src: np.ndarray, dst: np.ndarray, rng):
    """The same graph with its pages renamed by a permutation drawn from
    ``rng``, edges sorted by (source, destination) again: only the names,
    and so the memory layout of the edge list, differ."""
    perm = rng.permutation(n)
    key = perm[src].astype(np.int64) * n + perm[dst]
    key.sort()
    return n, (key // n).astype(np.int32), (key % n).astype(np.int32)


def build(spec: dict):
    """The configuration's graph: the web graph, in its back-button form
    when ``spec["back_button"]``."""
    n, src, dst = webgraph(spec)
    if spec.get("back_button"):
        n, src, dst = back_button(n, src, dst)
    return n, src, dst
