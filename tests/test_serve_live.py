"""A live graph: changesets of links and new pages rolled into a serving
``RankService`` while traffic flows, held to a plain numpy float64
reference ranked on each graph version.

The reference takes only the edge list of each version and a root set:
Kleinberg's base set (the roots, plus at most ``CAP`` out- and ``CAP``
in-neighbours of each root, the smallest ids where there are more), its
induced subgraph, and the paper's accelerated HITS by power iteration.
"""
import threading
import time

import numpy as np
import pytest

from repro.graph import Graph, WebGraphSpec, generate_webgraph
from repro.graph import subgraph as subgraph_mod
from repro.launch.serve_rank import load_delta_file, roll_delta
from repro.serve import RankService, RankServiceConfig
from repro.serve import rank_service as rank_service_mod
from repro.serve.delta import EdgeDelta, apply_to_graph, lookup_weights

CAP = 6
L1 = 1e-10


# ------------------------------------------------------------ reference


def ref_weights(indeg, outdeg):
    """The paper's eq. 2-3 weights; pages with no links get 0."""
    indeg, outdeg = indeg.astype(float), outdeg.astype(float)
    deg = indeg + outdeg
    ca, ch = np.zeros(len(deg)), np.zeros(len(deg))
    live = deg > 0
    ca[live], ch[live] = indeg[live] / deg[live], outdeg[live] / deg[live]
    gap = np.abs(indeg - outdeg)
    more_in, more_out = indeg > outdeg, indeg < outdeg
    ca[more_in] *= gap[more_in]
    ch[more_in] /= gap[more_in]
    ca[more_out] /= gap[more_out]
    ch[more_out] *= gap[more_out]
    return ca, ch


def ref_rank(version, roots):
    """(base set, authority, hub) of ``roots`` on ``version``, an
    ``(n, src, dst)`` edge list."""
    _n, src, dst = version
    roots = np.unique(np.asarray(roots, np.int64))
    parts = [roots]
    for r in roots:
        parts.append(np.sort(dst[src == r])[:CAP])
        parts.append(np.sort(src[dst == r])[:CAP])
    nodes = np.unique(np.concatenate(parts))
    keep = np.isin(src, nodes) & np.isin(dst, nodes)
    s, d = np.searchsorted(nodes, src[keep]), np.searchsorted(nodes, dst[keep])
    k = len(nodes)
    ca, ch = ref_weights(np.bincount(d, minlength=k),
                         np.bincount(s, minlength=k))
    h, a = np.full(k, 1.0 / k), np.zeros(k)
    for _ in range(100_000):
        a = np.bincount(d, weights=(h * ch)[s], minlength=k) * 1.0
        h_new = np.bincount(s, weights=(a * ca)[d], minlength=k) * 1.0
        h_new /= np.abs(h_new).sum() + 1e-300
        moved = np.abs(h_new - h).sum()
        h = h_new
        if moved <= 1e-14:
            break
    return nodes, a / (np.abs(a).sum() + 1e-300), h


def assert_matches(r, versions):
    """``r`` equals the reference at the version it is stamped with."""
    nodes, a, h = ref_rank(versions[r.graph_version], r.roots)
    assert np.array_equal(r.nodes, nodes), r.graph_version
    assert np.abs(r.authority - a).sum() <= L1
    assert np.abs(r.hub - h).sum() <= L1


class Crawl:
    """The edge list at each version, and changesets drawn against it:
    ``versions[v]`` is ``(n, src, dst)`` after the v-th changeset."""

    def __init__(self, g, seed):
        self.rng = np.random.default_rng(seed)
        self.versions = [(g.n_nodes, g.src.astype(np.int64),
                          g.dst.astype(np.int64))]

    def draw(self, adds=3, removes=1, pages=0, link_to=None):
        """A changeset of ``adds`` new links, ``removes`` removed ones and
        ``pages`` new pages, each given an in-link and an out-link (from
        and to ``link_to`` where given)."""
        n, src, dst = self.versions[-1]
        have = set(zip(src.tolist(), dst.tolist()))
        new = []
        for p in range(n, n + pages):
            u = int(src[self.rng.integers(len(src))]) if link_to is None \
                else int(link_to)
            new += [(u, p), (p, u)]
        while len(new) < adds + 2 * pages:
            s, d = (int(x) for x in self.rng.integers(n, size=2))
            if s != d and (s, d) not in have and (s, d) not in new:
                new.append((s, d))
        gone = [(int(src[i]), int(dst[i])) for i in
                self.rng.choice(len(src), size=removes, replace=False)]
        keep = ~np.isin(src * (n + pages) + dst,
                        [s * (n + pages) + d for s, d in gone])
        add = np.array(new, np.int64).reshape(-1, 2)
        self.versions.append((n + pages,
                              np.concatenate([src[keep], add[:, 0]]),
                              np.concatenate([dst[keep], add[:, 1]])))
        return {"adds": new, "removes": gone, "pages": pages}


@pytest.fixture(scope="module")
def g():
    """Distinct links, no self-loops, sorted by (source, destination)."""
    g = generate_webgraph(WebGraphSpec(300, 2400, 0.4, seed=5)).dedup()
    keep = g.src != g.dst
    return Graph(g.n_nodes, g.src[keep], g.dst[keep])


def make(g, **kw):
    kw = {"v_max": 4, "tol": 1e-12, "out_cap": CAP, "in_cap": CAP, **kw}
    return RankService(g, RankServiceConfig(**kw))


def root_sets(n, count, seed, size=3):
    rng = np.random.default_rng(seed)
    return [rng.choice(n, size=size, replace=False) for _ in range(count)]


# ------------------------------------------------ versions and pages


def test_deltas_with_new_pages_match_reference_at_each_version(g):
    """Through changesets that add links, remove links and add pages,
    every answer carries the version the roll acknowledged and equals the
    reference there: base sets exactly, vectors within 1e-10 L1."""
    svc, crawl = make(g), Crawl(g, seed=1)
    queries = root_sets(g.n_nodes, 8, seed=2)
    assert svc.graph_version == 0
    for r in svc.rank(queries):
        assert r.graph_version == 0
        assert_matches(r, crawl.versions)
    for k, spec in enumerate([dict(adds=6), dict(adds=0, removes=5),
                              dict(adds=2, pages=2),
                              dict(adds=4, removes=2, pages=1)], 1):
        ack = roll_delta(svc, crawl.draw(**spec))
        assert ack["version"] == svc.graph_version == k
        assert svc.g.n_nodes == crawl.versions[k][0]
        for r in svc.rank(queries):
            assert r.graph_version == k
            assert_matches(r, crawl.versions)
    snap = svc.telemetry_snapshot()
    assert snap["service.graph_version"] == 4
    assert snap["delta.pages_added"] == 3
    assert snap["delta.links_added"] == 6 + 0 + (2 + 4) + (4 + 2)
    assert snap["delta.links_removed"] == 1 + 5 + 1 + 2


def test_new_page_enters_base_set_and_can_be_a_root(g):
    """A page a delta adds is ranked where it enters a base set, and can
    itself be a root; before the delta it is no page at all."""
    svc, crawl = make(g), Crawl(g, seed=3)
    # a root whose lists the caps do not cut: the page is the largest id
    outdeg = np.bincount(g.src, minlength=g.n_nodes)
    indeg = np.bincount(g.dst, minlength=g.n_nodes)
    root = int(np.flatnonzero((outdeg > 0) & (outdeg < CAP)
                              & (indeg < CAP))[0])
    page = g.n_nodes
    with pytest.raises(ValueError, match="root ids"):
        svc.validate_roots([page])
    roll_delta(svc, crawl.draw(adds=0, removes=0, pages=1, link_to=root))
    before = svc.rank([np.array([root])])[0]
    assert page in before.nodes.tolist()
    assert_matches(before, crawl.versions)
    at = list(before.nodes).index(page)
    assert before.authority[at] > 0 and before.hub[at] > 0
    own = svc.rank([np.array([page, root])])[0]
    assert page in own.roots.tolist()
    assert_matches(own, crawl.versions)


def test_page_rows_reach_the_warm_table_and_validation(g):
    svc = make(g)
    roll_delta(svc, {"pages": 3})
    assert svc.g.n_nodes == g.n_nodes + 3
    assert len(svc._warm_h) == len(svc._warm_seen) == g.n_nodes + 3
    assert svc.validate_roots([g.n_nodes + 2]).tolist() == [g.n_nodes + 2]
    with pytest.raises(ValueError, match="outside"):
        svc.apply_edge_delta(adds=[(0, g.n_nodes + 4)], pages=1)
    with pytest.raises(ValueError, match="pages"):
        svc.apply_edge_delta(pages=-1)
    assert svc.graph_version == 1  # a refused changeset swaps nothing


def test_pages_rekey_a_weight_table():
    """A weighted table keeps every weight when pages grow the id space."""
    g = Graph(3, np.array([0, 1, 2]), np.array([1, 2, 0]))
    g1, t1 = apply_to_graph(g, None, EdgeDelta.normalize(
        reweights=[(1, 2, 3.0)], n_nodes=3))
    g2, t2 = apply_to_graph(g1, t1, EdgeDelta.normalize(
        adds=[(3, 0), (2, 4, 0.5)], n_nodes=3, pages=2))
    assert g2.n_nodes == 5 and g2.n_edges == 5
    w = lookup_weights(t2, 5, g2.src, g2.dst)
    got = dict(zip(zip(g2.src.tolist(), g2.dst.tolist()), w.tolist()))
    assert got == {(0, 1): 1.0, (1, 2): 3.0, (2, 0): 1.0, (3, 0): 1.0,
                   (2, 4): 0.5}
    # links and pages alone build an all-1.0 table over the new ids
    g3, t3 = apply_to_graph(g, None, EdgeDelta.normalize(
        adds=[(0, 3)], removes=[(2, 0)], n_nodes=3, pages=1))
    assert lookup_weights(t3, 4, g3.src, g3.dst).tolist() == [1.0, 1.0, 1.0]


# ------------------------------------------------ the roll under load


class SlowExtractor(subgraph_mod.SubgraphExtractor):
    """An extractor whose build takes long enough for submits to land
    inside the roll."""

    def __init__(self, *a, **k):
        time.sleep(0.3)
        super().__init__(*a, **k)


def test_roll_under_load_refuses_nothing_and_never_serves_stale(
        g, monkeypatch):
    """Requests flow through the queue while four rolls run: none is
    refused, including those submitted inside a roll; none is answered at
    a version older than the one acknowledged before its submit; each
    answer equals the reference at its own version."""
    svc, crawl = make(g), Crawl(g, seed=4)
    queries = root_sets(g.n_nodes, 48, seed=5)
    svc.rank(queries[:4])  # compile the shapes before the clock matters
    monkeypatch.setattr(rank_service_mod, "SubgraphExtractor",
                        SlowExtractor)
    specs = [crawl.draw(adds=4, removes=1, pages=k % 2) for k in range(4)]
    acked, sent = [0], []
    with svc.queue(deadline_ms=2) as q:
        def crawler():
            for spec in specs:
                time.sleep(0.1)
                acked.append(roll_delta(svc, spec)["version"])

        th = threading.Thread(target=crawler)
        th.start()
        for roots in queries:
            at, before = time.perf_counter(), acked[-1]
            sent.append((at, before, q.submit(roots)))
            time.sleep(0.04)
        th.join()
        results = [(at, before, t.result(timeout=120))
                   for at, before, t in sent]
    assert acked == [0, 1, 2, 3, 4]
    rolls = [(t0, t1) for _v, stage, t0, t1 in svc.delta_trace
             if stage == "roll"]
    assert len(rolls) == 4
    during = [r for at, _b, r in results
              if any(t0 <= at <= t1 for t0, t1 in rolls)]
    assert during, "no submit landed inside a roll"
    versions = set()
    for _at, before, r in results:
        assert r is not None and r.status != "shed"
        assert r.graph_version >= before
        versions.add(r.graph_version)
        assert_matches(r, crawl.versions)
    assert len(versions) >= 3  # traffic really spanned the rolls


def test_batch_assembled_before_swap_is_ranked_on_one_version_and_not_cached(
        g):
    """A batch assembled at version 0 whose sweep outlasts a roll that
    touches it is published stamped 0, equal to the reference at 0 for
    every column, and kept out of the cache: the next request for the
    same root set is computed anew at version 1, and a request submitted
    after the acknowledgement carries version 1 while the old batch is
    still in flight."""
    svc, crawl = make(g), Crawl(g, seed=6)
    queries = root_sets(g.n_nodes, 3, seed=7)
    svc.rank(queries)  # compile; the cache now holds all three
    svc.clear_result_cache()
    gate, entered = threading.Event(), threading.Event()
    sweep = svc.pipeline.sweep

    def held(asm):
        entered.set()
        gate.wait(60)
        return sweep(asm)

    svc.pipeline.sweep = held
    out = {}
    th = threading.Thread(target=lambda: out.update(old=svc.rank(queries)))
    th.start()
    assert entered.wait(60)
    # a link between two roots of each root set: it touches every one
    adds = [(int(q[0]), int(q[1])) for q in queries]
    assert not any(((g.src == s) & (g.dst == d)).any() for s, d in adds)
    n, src, dst = crawl.versions[0]
    crawl.versions.append((n, np.concatenate([src, [s for s, _ in adds]]),
                           np.concatenate([dst, [d for _, d in adds]])))
    assert roll_delta(svc, {"adds": adds})["version"] == 1
    svc.pipeline.sweep = sweep
    with svc.queue(deadline_ms=1) as q:
        fresh = q.submit(queries[0]).result(timeout=60)
        assert not gate.is_set() and th.is_alive()  # the old one waits
    gate.set()
    th.join(60)
    old = out["old"]
    assert [r.graph_version for r in old] == [0, 0, 0]
    for r in old:
        assert_matches(r, crawl.versions)
    assert fresh.graph_version == 1 and fresh.status != "hit"
    assert_matches(fresh, crawl.versions)
    again = svc.rank(queries)
    assert [r.graph_version for r in again] == [1, 1, 1]
    # the one computed at version 1 was cached; the old batch's were not
    assert [r.status == "hit" for r in again] == [True, False, False]
    for r in again:
        assert_matches(r, crawl.versions)
    assert svc.telemetry_snapshot()["service.stale_uncached"] == 3


def test_cache_hit_carries_the_current_version(g):
    """An entry a roll did not touch keeps serving, stamped with the
    version current at the probe."""
    svc = make(g)
    roots = np.array([1, 2])
    svc.rank([roots])
    fs = svc.extractor.extract(roots)
    far = np.setdiff1d(np.arange(g.n_nodes), fs.nodes)[:2]
    roll_delta(svc, {"adds": [(int(far[0]), int(far[1]))]})
    r = svc.rank([roots])[0]
    assert r.status == "hit" and r.graph_version == 1


class DoorLock:
    """The spill IO lock, with one named thread held at its door until
    ``open`` is set: the gap in which a roll's swap can run."""

    def __init__(self, lock, thread_name):
        self._lock, self._name = lock, thread_name
        self.at_door, self.open = threading.Event(), threading.Event()

    def __enter__(self):
        if threading.current_thread().name == self._name:
            self.at_door.set()
            assert self.open.wait(60)
        self._lock.acquire()
        return self

    def __exit__(self, *exc):
        self._lock.release()


@pytest.mark.parametrize("policy,writer", [
    ("all", "_drain_spill"), ("evict", "_drain_spill"),
    ("evict", "flush_spill")])
def test_spill_write_pending_across_a_roll_never_serves_stale(
        g, tmp_path, policy, writer):
    """A pre-delta vector on its way to the spill when a roll that touches
    it swaps in never reaches the disk under the new generation, even
    when the write began before the swap: the next request for its root
    set is computed anew at the new version and equals the reference
    there. The writes: a deferred write drained (under "evict" an
    evictee, which the cache no longer holds at the swap), and a
    shutdown flush of the cache."""
    drained = writer == "_drain_spill"
    svc, crawl = make(g, spill_dir=str(tmp_path), spill_policy=policy,
                      cache_size=1 if drained else 64), Crawl(g, seed=11)
    x, y = root_sets(g.n_nodes, 2, seed=12)
    if drained:
        svc._drain_spill = lambda: None  # let the writes pile up
    key = svc.rank([x])[0].key
    if drained:
        svc.rank([y])  # evicts x's entry into the pending writes
        del svc._drain_spill
        assert any(p[0] == key for p in svc._spill_pending)
    door = DoorLock(svc._spill_io_lock, "slow-writer")
    svc._spill_io_lock = door
    write = threading.Thread(target=getattr(svc, writer), name="slow-writer")
    write.start()
    assert door.at_door.wait(60)
    # a link between two of x's roots touches its every answer
    add = (int(x[0]), int(x[1]))
    if ((g.src == add[0]) & (g.dst == add[1])).any():
        add = add[::-1]
    assert not ((g.src == add[0]) & (g.dst == add[1])).any()
    n, src, dst = crawl.versions[0]
    crawl.versions.append((n, np.append(src, add[0]), np.append(dst, add[1])))
    assert roll_delta(svc, {"adds": [add]})["version"] == 1
    door.open.set()
    write.join(60)
    assert not write.is_alive()
    assert svc._spill.get(key) is None
    r = svc.rank([x])[0]
    assert r.graph_version == 1 and r.status != "hit"
    assert_matches(r, crawl.versions)

def test_roll_spans_nest_and_feed_their_histograms(g):
    svc = make(g)
    ack = roll_delta(svc, {"adds": [(0, 1)] if not (
        (g.src == 0) & (g.dst == 1)).any() else [(1, 0)], "pages": 1})
    spans = {stage: (t0, t1) for v, stage, t0, t1 in svc.delta_trace
             if v == ack["version"]}
    assert set(spans) == {"roll", "apply", "extract", "swap"}
    r0, r1 = spans["roll"]
    for stage in ("apply", "extract", "swap"):
        assert r0 <= spans[stage][0] <= spans[stage][1] <= r1
    assert spans["apply"][1] <= spans["extract"][0] <= spans["swap"][0]
    snap = svc.telemetry_snapshot()
    for stage in ("roll", "apply", "extract", "swap"):
        assert snap[f"service.delta.{stage}_ms"]["count"] == 1
    assert ack["roll_ms"] >= ack["swap_ms"] > 0


def test_delta_file_takes_pages(tmp_path, g):
    p = tmp_path / "delta.json"
    p.write_text('{"adds": [[0, %d]], "pages": 1}' % g.n_nodes)
    spec = load_delta_file(str(p))
    assert spec["pages"] == 1
    svc = make(g)
    ack = roll_delta(svc, spec)
    assert ack["version"] == 1 and ack["pages"] == 1
    assert svc.g.n_nodes == g.n_nodes + 1


def test_concurrent_rolls_lose_no_changeset(g):
    """More rolling threads than cores, beside a ranking thread, with a
    short switch interval: every roll gets its own version and every
    link lands (a lost update would drop one)."""
    import os
    import sys
    svc = make(g)
    have = set(zip(g.src.tolist(), g.dst.tolist()))
    rng = np.random.default_rng(9)
    links = []
    while len(links) < 2 * (os.cpu_count() or 4):
        s, d = (int(x) for x in rng.integers(g.n_nodes, size=2))
        if s != d and (s, d) not in have and (s, d) not in links:
            links.append((s, d))
    acks, stop = [], threading.Event()

    def ranker():
        queries = root_sets(g.n_nodes, 4, seed=10)
        while not stop.is_set():
            svc.rank(queries)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        rk = threading.Thread(target=ranker)
        rk.start()
        rollers = [threading.Thread(target=lambda link=link: acks.append(
            roll_delta(svc, {"adds": [link]})["version"])) for link in links]
        for t in rollers:
            t.start()
        for t in rollers:
            t.join(60)
        stop.set()
        rk.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not rk.is_alive() and not any(t.is_alive() for t in rollers)
    assert sorted(acks) == list(range(1, len(links) + 1))
    assert svc.graph_version == len(links)
    assert set(links) <= set(zip(svc.g.src.tolist(), svc.g.dst.tolist()))
