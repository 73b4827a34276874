"""Live edge deltas: weight-only patch vs replan, structural rebuilds,
warm-start carryover, spill generation fencing, and changeset validation.

The oracle throughout is "a fresh service with the delta applied before
any traffic" — the delta path must be indistinguishable from having
started with the post-delta graph (<= 1e-10), while reusing far more
cached state.
"""
import numpy as np
import pytest

from repro.graph import Graph, WebGraphSpec, generate_webgraph
from repro.serve import RankService, RankServiceConfig
from repro.serve.delta import EdgeDelta, apply_to_graph, lookup_weights

TOL = 1e-10


@pytest.fixture(scope="module")
def g():
    return generate_webgraph(WebGraphSpec(1500, 12000, 0.4, seed=7))


def make(g, backend="dense", **kw):
    return RankService(g, RankServiceConfig(v_max=4, tol=TOL,
                                            backend=backend, **kw))


def union_edge(svc, roots):
    """A (src, dst) global edge inside this root set's union subgraph —
    reweighting it changes what this query serves."""
    fs = svc.extractor.extract(np.asarray(roots))
    return (int(fs.nodes[fs.graph.src[0]]), int(fs.nodes[fs.graph.dst[0]]))


def assert_close(r, o, tol=TOL):
    assert (r.nodes == o.nodes).all()
    assert float(np.abs(r.authority - o.authority).max()) <= tol
    assert float(np.abs(r.hub - o.hub).max()) <= tol


# ------------------------------------------------ weight-only: patch path


@pytest.mark.parametrize("backend", ["dense", "bsr"])
def test_weight_delta_patches_plan_and_matches_cold_oracle(g, backend):
    """A reweight-only delta must serve post-delta-correct results
    (<=1e-10 vs a service that never saw the pre-delta graph) WITHOUT
    rebuilding the surviving plan: the patched counter fires and
    plan_misses stays where cold traffic left it."""
    svc = make(g, backend=backend)
    roots = np.array([1, 2, 3])
    svc.rank([roots])
    u, v = union_edge(svc, roots)
    misses_before = svc.stats["plan_misses"]

    summ = svc.apply_edge_delta(reweights=[(u, v, 2.0)])
    assert summ["structural"] is False
    assert summ["invalidated"] >= 1
    r = svc.rank([roots])[0]
    assert r.status != "hit"  # pre-delta result must not be served

    snap = svc.telemetry_snapshot()
    assert snap["service.delta.patched"][backend] >= 1
    assert svc.stats["plan_misses"] == misses_before
    assert snap["service.delta.swap_ms"]["count"] == 1

    oracle = make(g, backend=backend)
    oracle.apply_edge_delta(reweights=[(u, v, 2.0)])
    assert_close(r, oracle.rank([roots])[0])


def test_sharded_weight_delta_patches_and_matches_oracle(g):
    """The sharded patch hook revalues the pow2-bucketed device shards
    in place: a reweight-only delta fires the patched counter (never
    replanned) and the served fixed point matches the cold oracle."""
    svc = make(g, backend="sharded", shard_devices=1)
    roots = np.array([4, 5, 6])
    svc.rank([roots])
    u, v = union_edge(svc, roots)

    svc.apply_edge_delta(reweights=[(u, v, 3.0)])
    r = svc.rank([roots])[0]
    snap = svc.telemetry_snapshot()
    assert snap["service.delta.patched"]["sharded"] >= 1
    assert snap["service.delta.replanned"] == 0

    oracle = make(g, backend="sharded", shard_devices=1)
    oracle.apply_edge_delta(reweights=[(u, v, 3.0)])
    assert_close(r, oracle.rank([roots])[0])


def test_patch_vs_replan_parity(g):
    """The patched plan computes the same fixed point a from-scratch
    rebuild would: dense (patched) vs a plan-cache-disabled service
    (every batch rebuilt) after the same delta."""
    svc = make(g, backend="dense")
    roots = np.array([7, 8, 9])
    svc.rank([roots])
    u, v = union_edge(svc, roots)
    svc.apply_edge_delta(reweights=[(u, v, 0.5)])
    r = svc.rank([roots])[0]
    assert svc.telemetry_snapshot()["service.delta.patched"]["dense"] >= 1

    rebuilt = make(g, backend="dense", plan_cache_size=0)
    rebuilt.apply_edge_delta(reweights=[(u, v, 0.5)])
    assert_close(r, rebuilt.rank([roots])[0])


# ------------------------------------------------ structural deltas


def test_structural_add_remove_matches_plain_graph_oracle(g):
    """Adds at the default weight 1.0 and removes must rank exactly like
    a service constructed on the post-delta edge list (no weight table in
    sight — the unweighted path is the oracle)."""
    svc = make(g, backend="dense")
    roots = np.array([10, 11, 12])
    svc.rank([roots])
    u, v = union_edge(svc, roots)
    add = (int(roots[0]), (v + 1) % g.n_nodes)

    summ = svc.apply_edge_delta(adds=[add], removes=[(u, v)])
    assert summ["structural"] is True
    r = svc.rank([roots])[0]

    keep = ~((np.asarray(g.src) == u) & (np.asarray(g.dst) == v))
    g2 = Graph(g.n_nodes,
               np.concatenate([g.src[keep], [add[0]]]),
               np.concatenate([g.dst[keep], [add[1]]]))
    assert_close(r, make(g2, backend="dense").rank([roots])[0])


def test_untouched_entries_survive_structural_delta(g):
    """A structural delta outside a query's union leaves its cached
    result (and plan) serving: zero-downtime rolls only pay for what the
    delta touched."""
    svc = make(g, backend="dense")
    roots = np.array([20, 21])
    svc.rank([roots])
    fs = svc.extractor.extract(roots)
    outside = np.setdiff1d(np.arange(g.n_nodes), fs.nodes)[:2]
    misses_before = svc.stats["plan_misses"]

    summ = svc.apply_edge_delta(adds=[(int(outside[0]), int(outside[1]))])
    assert summ["invalidated"] == 0
    r = svc.rank([roots])[0]
    assert r.status == "hit"
    assert svc.stats["plan_misses"] == misses_before


def test_add_of_existing_pair_is_reweight(g):
    """Re-adding a live pair with a new weight == reweighting it
    (idempotent operator rolls), down to the served fixed point."""
    svc_a = make(g)
    svc_r = make(g)
    roots = np.array([30, 31, 32])
    u, v = union_edge(svc_a, roots)
    svc_a.apply_edge_delta(adds=[(u, v, 2.5)])
    svc_r.apply_edge_delta(reweights=[(u, v, 2.5)])
    assert_close(svc_a.rank([roots])[0], svc_r.rank([roots])[0])


# ------------------------------------------------ warm-start carryover


def test_warm_start_carries_over_a_delta(g):
    """The tentpole's payoff: after a small reweight, the refresh starts
    from the pre-delta fixed point (status "warm") and converges in
    fewer sweeps than the cold build did."""
    svc = make(g, backend="dense")
    roots = np.array([40, 41, 42])
    cold = svc.rank([roots])[0]
    assert cold.status == "cold"
    u, v = union_edge(svc, roots)

    svc.apply_edge_delta(reweights=[(u, v, 1.05)])
    warm = svc.rank([roots])[0]
    assert warm.status == "warm"
    assert 0 < warm.iters < cold.iters

    oracle = make(g, backend="dense")
    oracle.apply_edge_delta(reweights=[(u, v, 1.05)])
    assert_close(warm, oracle.rank([roots])[0])


# ------------------------------------------------ spill generation fence


def test_restart_after_delta_never_serves_predelta_vectors(g, tmp_path):
    """Spilled pre-delta vectors are generation-fenced: a restart onto
    the same spill dir must not resurrect them, and the refreshed answer
    matches the cold post-delta oracle."""
    spill = str(tmp_path / "spill")
    roots = np.array([50, 51, 52])
    svc = make(g, spill_dir=spill, spill_policy="all")
    svc.rank([roots])
    svc.flush_spill()
    assert svc.stats["spill_writes"] >= 1
    u, v = union_edge(svc, roots)
    summ = svc.apply_edge_delta(reweights=[(u, v, 2.0)])
    assert summ["data_generation"] == 1

    svc2 = make(g, spill_dir=spill, spill_policy="all")
    assert svc2.stats["spill_restored"] == 0
    svc2.apply_edge_delta(reweights=[(u, v, 2.0)])
    r = svc2.rank([roots])[0]
    assert r.status == "cold"
    assert svc2.stats["spill_hits"] == 0

    oracle = make(g)
    oracle.apply_edge_delta(reweights=[(u, v, 2.0)])
    assert_close(r, oracle.rank([roots])[0])


def test_delta_respills_survivors_under_new_generation(g, tmp_path):
    """Entries the delta did NOT touch are re-spilled under the post-delta
    generation, so a restart still serves them warm from disk."""
    spill = str(tmp_path / "spill")
    svc = make(g, spill_dir=spill, spill_policy="all")
    touched_roots = np.array([60, 61])
    safe_roots = np.array([62, 63])
    svc.rank([touched_roots, safe_roots])
    svc.flush_spill()
    fs_t = svc.extractor.extract(touched_roots)
    safe = set(svc.extractor.extract(safe_roots).nodes.tolist())
    edge = next(((int(fs_t.nodes[s]), int(fs_t.nodes[d]))
                 for s, d in zip(fs_t.graph.src, fs_t.graph.dst)
                 if int(fs_t.nodes[s]) not in safe
                 and int(fs_t.nodes[d]) not in safe), None)
    assert edge is not None, "no union edge isolable from the safe query"
    svc.apply_edge_delta(reweights=[(edge[0], edge[1], 2.0)])

    svc2 = make(g, spill_dir=spill, spill_policy="all")
    assert svc2.stats["spill_restored"] == 1  # survivor only, new gen
    r = svc2.rank([safe_roots])[0]
    assert r.status == "hit"


def test_clear_result_cache_clears_disk_fallback_too(g, tmp_path):
    """Satellite bugfix: clear_result_cache() bumps the spill generation,
    so cleared state stays cleared across the disk-fallback path AND a
    restart — previously the next miss would resurrect it from disk."""
    spill = str(tmp_path / "spill")
    roots = np.array([70, 71, 72])
    svc = make(g, spill_dir=spill, spill_policy="all")
    svc.rank([roots])
    svc.flush_spill()
    assert svc.rank([roots])[0].status == "hit"

    svc.clear_result_cache()
    # a restart right now must restore nothing (disk copies are fenced
    # behind the old generation) ...
    svc2 = make(g, spill_dir=spill, spill_policy="all")
    assert svc2.stats["spill_restored"] == 0
    # ... and the live service's disk fallback must miss too
    r = svc.rank([roots])[0]
    assert r.status == "cold"  # not "hit": disk copy is old-generation
    assert svc.stats["spill_hits"] == 0


# ------------------------------------------------ roots dedupe (satellite)


def test_duplicate_roots_rank_identically_to_deduped(g):
    """validate_roots dedupes: [a, a, b] is the same query as [a, b] —
    same cache entry, same vectors, no double-counted root mass."""
    svc = make(g)
    a, b = 80, 81
    dup = svc.rank([np.array([a, a, b])])[0]
    ded = svc.rank([np.array([a, b])])[0]
    assert ded.status == "hit"  # literally the same cache entry
    assert (dup.roots == np.array([a, b])).all()
    assert_close(dup, ded, tol=0.0)

    va = svc.validate_roots([a, a, b])
    assert (va == np.array([a, b])).all()


# ------------------------------------------------ changeset validation


def test_delta_validation_errors(g):
    svc = make(g)
    u, v = union_edge(svc, np.array([1, 2]))
    absent = (0, 0) if not ((g.src == 0) & (g.dst == 0)).any() else (0, 1)
    with pytest.raises(ValueError, match="not in the graph"):
        svc.apply_edge_delta(removes=[absent])
    with pytest.raises(ValueError, match="not in the graph"):
        svc.apply_edge_delta(reweights=[(absent[0], absent[1], 2.0)])
    with pytest.raises(ValueError, match="finite and nonzero"):
        svc.apply_edge_delta(reweights=[(u, v, 0.0)])
    with pytest.raises(ValueError, match="finite and nonzero"):
        svc.apply_edge_delta(adds=[(u, v, float("nan"))])
    with pytest.raises(ValueError, match="outside"):
        svc.apply_edge_delta(removes=[(u, g.n_nodes)])
    with pytest.raises(ValueError, match="want"):
        svc.apply_edge_delta(reweights=[(u, v)])  # weight required
    # nothing above mutated the service
    assert svc.telemetry_snapshot()["service.delta.swap_ms"]["count"] == 0


def test_empty_delta_is_a_noop(g):
    svc = make(g)
    roots = np.array([90, 91])
    svc.rank([roots])
    summ = svc.apply_edge_delta()
    assert summ == {"version": 0, "structural": False, "pages": 0,
                    "invalidated": 0, "touched_nodes": 0,
                    "data_generation": None, "swap_ms": 0.0,
                    "roll_ms": 0.0}
    assert svc.graph_version == 0
    assert svc.rank([roots])[0].status == "hit"


def test_apply_to_graph_is_pure_and_last_add_wins():
    g = Graph(4, np.array([0, 1, 2]), np.array([1, 2, 3]))
    delta = EdgeDelta.normalize(adds=[(0, 3, 2.0), (0, 3, 5.0)],
                                removes=[(2, 3)], n_nodes=4)
    assert delta.structural
    assert (delta.touched_nodes() == np.array([0, 2, 3])).all()
    g2, (keys, vals) = apply_to_graph(g, None, delta)
    # pure: the input graph is untouched
    assert g.n_edges == 3 and g2.n_edges == 3
    pairs = set(zip(g2.src.tolist(), g2.dst.tolist()))
    assert pairs == {(0, 1), (1, 2), (0, 3)}
    w = lookup_weights((keys, vals), 4, g2.src, g2.dst)
    got = dict(zip(zip(g2.src.tolist(), g2.dst.tolist()), w.tolist()))
    assert got[(0, 3)] == 5.0  # last occurrence wins
    assert got[(0, 1)] == 1.0
