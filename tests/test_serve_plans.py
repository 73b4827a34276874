"""SweepPlan cache parity/property suite (ISSUE 4 tentpole lockdown).

Plan-cached serving must be *invisible* semantically: for any graph, any
backend, any device layout, and any root-set sequence — including repeats,
evictions, warm-starts-after-evict, and graph mutations — results through
the plan cache match a cold-built (plan-cache-disabled) service to <=1e-10
L1. Structure keys hash the actual padded edge structure, so a mutated
graph can never be served a stale plan. Sharded device matrices run in a
subprocess with ``--xla_force_host_platform_device_count=8`` (as in
test_serve_backends).
"""
import dataclasses
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.weights import accel_weights
from repro.graph import WebGraphSpec, generate_webgraph
from repro.serve import (PlanCache, RankService, RankServiceConfig,
                         ShardedSweepBackend, SweepBatch, shared_mesh)
from repro.serve.backends import DenseSweepBackend

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-12


def cfg(**kw):
    kw.setdefault("v_max", 4)
    kw.setdefault("tol", TOL)
    return RankServiceConfig(**kw)


def assert_results_match(res, ref, label=""):
    for a, b in zip(res, ref):
        assert (a.nodes == b.nodes).all(), label
        assert a.status == b.status, (label, a.status, b.status)
        assert a.iters == b.iters, (label, a.iters, b.iters)
        assert np.abs(a.authority - b.authority).sum() <= 1e-10, label
        assert np.abs(a.hub - b.hub).sum() <= 1e-10, label


# ----------------------------------------------- cached == cold (property)


@given(st.integers(0, 10**6), st.integers(2, 5), st.integers(2, 4))
@settings(max_examples=6, deadline=None)
def test_plan_cached_matches_cold_built(seed, n_roots, n_queries):
    """Random graph x random root-set sequence: a plan-cached service and a
    plan-disabled one produce identical statuses, iteration counts, and
    scores (<=1e-10 L1) through cold, repeat (cache-hit), and refresh
    (plan-hit) passes — dense and bsr, in process."""
    rng = np.random.default_rng(seed)
    g = generate_webgraph(WebGraphSpec(150, 1000, 0.5,
                                       seed=int(rng.integers(1 << 30))))
    queries = [rng.choice(g.n_nodes, size=n_roots, replace=False)
               for _ in range(n_queries)]
    for backend in ("dense", "bsr"):
        ref = RankService(g, cfg(backend=backend, plan_cache_size=0))
        svc = RankService(g, cfg(backend=backend, plan_cache_size=8))
        assert_results_match(svc.rank(queries), ref.rank(queries),
                             f"{backend}/cold")
        assert_results_match(svc.rank(queries), ref.rank(queries),
                             f"{backend}/hit")
        # refresh re-sweeps the same unions: every batch hits the plan
        assert_results_match(svc.rank(queries, refresh=True),
                             ref.rank(queries, refresh=True),
                             f"{backend}/refresh")
        assert ref.stats["plan_hits"] == 0  # disabled cache never hits
        assert svc.stats["plan_misses"] >= 1
        assert svc.stats["plan_hits"] >= 1, svc.stats


# ------------------------------------- eviction / warm-start-after-evict


@pytest.mark.parametrize("backend", ["dense", "bsr", "sharded"])
def test_eviction_rebuild_and_warm_start_after_evict(backend):
    """plan_cache_size=1: alternating root sets evict each other's plans;
    the rebuilt plan serves results identical to the never-cached service,
    and an exact repeat after eviction still WARM-starts (the vector cache
    and the plan cache are independent layers)."""
    g = generate_webgraph(WebGraphSpec(300, 2200, 0.5, seed=5))
    q1 = np.arange(5)
    q2 = np.arange(200, 206)

    def run(plan_cache_size):
        svc = RankService(g, cfg(backend=backend,
                                 plan_cache_size=plan_cache_size))
        out = [svc.rank([q1]), svc.rank([q2]),
               svc.rank([q1], refresh=True), svc.rank([q2], refresh=True)]
        return svc, [r for batch in out for r in batch]

    ref_svc, ref = run(0)
    svc, res = run(1)
    assert_results_match(res, ref, backend)
    # the two unions alternate through a 1-entry cache: every refresh had
    # to rebuild (miss + eviction), never serving a stale or absent plan
    assert svc.stats["plan_evictions"] >= 2, svc.stats
    assert svc.stats["plan_misses"] == 4, svc.stats
    assert res[2].status == "warm" and res[3].status == "warm"
    assert ref_svc.stats["plan_evictions"] == 0


# ------------------------------------------- graph-mutation invalidation


def _hand_batch(edges, n_pad=16, w_scale=1.0, dtype=jnp.float64):
    """A v=1 padded batch over explicit edges (full-support mask except the
    dead pad row) — the unit harness for key/staleness checks."""
    e_pad = 16
    src = np.full(e_pad, n_pad - 1, np.int32)
    dst = np.full(e_pad, n_pad - 1, np.int32)
    w = np.zeros(e_pad)
    for i, (s, d) in enumerate(edges):
        src[i], dst[i], w[i] = s, d, w_scale
    m = np.ones((n_pad, 1))
    m[-1, 0] = 0.0
    sel = w != 0
    indeg = np.bincount(dst[sel], minlength=n_pad)
    outdeg = np.bincount(src[sel], minlength=n_pad)
    ca, ch = accel_weights(indeg, outdeg)
    h0 = m / m.sum()
    return SweepBatch(h0=h0, src=src, dst=dst, w=w,
                      ca=ca[:, None] * m, ch=ch[:, None] * m, mask=m,
                      tol=1e-12, max_iter=200, dtype=dtype)


def test_structure_key_tracks_every_structural_field():
    """The plan key must change with edges, weights, padding, and dtype —
    and must NOT change across identical rebuilds (else caching is dead)."""
    chain = [(0, 1), (1, 2), (2, 3), (3, 4)]
    star = [(0, 1), (0, 2), (0, 3), (0, 4)]
    b = _hand_batch(chain)
    assert b.structure_key() == _hand_batch(chain).structure_key()
    assert b.structure_key() != _hand_batch(star).structure_key()
    assert b.structure_key() != _hand_batch(chain,
                                            w_scale=2.0).structure_key()
    assert b.structure_key() != _hand_batch(chain, n_pad=32).structure_key()
    assert b.structure_key() != _hand_batch(
        chain, dtype=jnp.float32).structure_key()


def test_mutated_graph_never_serves_stale_plan():
    """A changed subgraph (same node ids, different edges) misses the plan
    cache; serving the mutated batch against the OLD plan would return the
    old graph's rankings — the bug the content-hash key exists to prevent."""
    be = DenseSweepBackend()
    b1 = _hand_batch([(0, 1), (1, 2), (2, 3), (3, 4)])
    b2 = _hand_batch([(0, 1), (0, 2), (0, 3), (0, 4)])
    cache = PlanCache(capacity=4)
    key1 = (be.name, be.plan_params(), b1.structure_key())
    cache.put(key1, be.plan(b1, b1.structure_key()))
    assert cache.get((be.name, be.plan_params(),
                      b2.structure_key())) is None  # mutation -> miss
    # the counterfactual: the stale plan computes the WRONG fixed point
    stale = be.sweep(cache.get(key1), b2)
    fresh = be.sweep(be.plan(b2), b2)
    assert np.abs(stale[1] - fresh[1]).sum() > 1e-3
    # while the cached plan still serves its own structure exactly
    again = be.sweep(cache.get(key1), b1)
    ref = be.converge(b1)
    assert np.abs(again[1] - ref[1]).sum() <= 1e-12


@pytest.mark.parametrize("path", ["patch", "restore"])
def test_dense_patched_and_restored_plans_match_fresh(path, tmp_path):
    """The dense plan holds the edges in order by destination and by
    source. A weight-only patch puts the new weights into both
    orders; a spilled plan comes back from disk in its order. Either sweeps
    to the same vectors, bit for bit, as a plan built fresh."""
    from repro.serve.spill import PlanSpill
    be = DenseSweepBackend()
    # keys out of order both ways, pages sharing in- and out-links
    edges = [(3, 1), (0, 2), (4, 1), (1, 3), (2, 1), (0, 4), (4, 3), (2, 0)]
    old = _hand_batch(edges)
    w = old.w.copy()
    w[:len(edges)] = np.arange(1.0, len(edges) + 1)
    new = dataclasses.replace(old, w=w)
    fresh = be.sweep(be.plan(new), new)
    if path == "patch":
        plan = be.patch(be.plan(old), new, new.structure_key())
        # the weights matter: the unpatched plan's vectors differ
        stale = be.sweep(be.plan(old), new)
        assert np.abs(stale[0] - fresh[0]).sum() > 1e-3
    else:
        spill = PlanSpill(str(tmp_path))
        spill.put(("dense", (), new.structure_key()),
                  *be.plan_arrays(be.plan(new)))
        plan = be.plan_restore(new.structure_key(), *spill.get(
            ("dense", (), new.structure_key())))
    for got, want in zip(be.sweep(plan, new), fresh):
        np.testing.assert_array_equal(got, want)


# ------------------------------------------------ PlanCache unit behavior


def test_plan_cache_lru_and_stats():
    c = PlanCache(capacity=2)
    for i in range(3):
        c.put((i,), f"plan{i}")
    assert len(c) == 2 and c.stats["evictions"] == 1
    assert c.get((0,)) is None          # evicted (oldest)
    assert c.get((2,)) == "plan2"
    assert c.get((1,)) == "plan1"       # touch: 1 becomes MRU
    c.put((3,), "plan3")                # evicts 2, not 1
    assert c.get((2,)) is None and c.get((1,)) == "plan1"
    assert c.stats["hits"] == 3 and c.stats["misses"] == 2
    disabled = PlanCache(capacity=0)
    disabled.put(("k",), "p")
    assert disabled.get(("k",)) is None and len(disabled) == 0


# -------------------------------------------- mesh identity (regression)


def test_sharded_mesh_built_once_and_shared():
    """Regression (ISSUE 4): mesh construction is hoisted into the shared
    memo + cached plan — repeat batches, repeat services, and fresh backend
    instances must all hold the SAME mesh object, never re-create it."""
    g = generate_webgraph(WebGraphSpec(200, 1400, 0.5, seed=7))
    q1, q2 = np.arange(4), np.arange(100, 104)
    svc = RankService(g, cfg(backend="sharded", shard_devices=1))
    svc.rank([q1])
    svc.rank([q2])  # second DISTINCT union -> second plan
    plans = list(svc._plans._plans.values())
    assert len(plans) == 2
    assert plans[0].mesh is plans[1].mesh  # one mesh across batches
    be = svc._backends["sharded"]
    assert plans[0].mesh is be.mesh
    # fresh instances and fresh services reuse it too (process-wide memo)
    assert ShardedSweepBackend(n_devices=1).mesh is be.mesh
    svc2 = RankService(g, cfg(backend="sharded", shard_devices=1))
    svc2.rank([q1])
    assert next(iter(svc2._plans._plans.values())).mesh is be.mesh
    assert shared_mesh(be.mesh.devices.flatten().tolist(),
                       ("data",)) is be.mesh


# ---------------------------------------- device matrix (subprocess, 8dev)


PLAN_MATRIX = r"""
import numpy as np, jax
jax.config.update("jax_enable_x64", True)
from repro.graph import WebGraphSpec, generate_webgraph
from repro.serve import RankService, RankServiceConfig

TOL = 1e-12
g = generate_webgraph(WebGraphSpec(220, 1600, 0.5, seed=3))
rng = np.random.default_rng(1)
queries = [rng.choice(g.n_nodes, size=4, replace=False) for _ in range(4)]

def run(plan_cache, **kw):
    svc = RankService(g, RankServiceConfig(
        v_max=2, tol=TOL, plan_cache_size=plan_cache, **kw))
    out = svc.rank(queries) + svc.rank(queries, refresh=True)
    return svc, out

assert len(jax.devices()) == 8, jax.devices()
configs = [("dense", {"backend": "dense"}), ("bsr", {"backend": "bsr"})]
for mode in ("replicated", "dual_blocked"):
    for s in (1, 2, 4, 8):
        configs.append((f"sharded/{mode}/{s}",
                        {"backend": "sharded", "shard_mode": mode,
                         "shard_devices": s}))
for label, kw in configs:
    ref_svc, ref = run(0, **kw)
    svc, res = run(8, **kw)
    for a, b in zip(res, ref):
        assert (a.nodes == b.nodes).all(), label
        assert a.status == b.status, (label, a.status, b.status)
        assert a.iters == b.iters, label
        assert np.abs(a.authority - b.authority).sum() <= 1e-10, label
        assert np.abs(a.hub - b.hub).sum() <= 1e-10, label
    assert ref_svc.stats["plan_hits"] == 0, label
    assert svc.stats["plan_misses"] >= 1, label
    assert svc.stats["plan_hits"] >= 1, (label, svc.stats)
    print("PLAN PARITY", label, "OK")
print("MATRIX OK")
"""


def test_plan_parity_device_matrix():
    """Plan-cached == cold-built on every backend x shard_mode x 1/2/4/8
    host devices, through cold, cache-hit, and refresh (plan-hit) passes."""
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", PLAN_MATRIX],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=600)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "MATRIX OK" in r.stdout
    for s in (1, 2, 4, 8):
        assert f"PLAN PARITY sharded/dual_blocked/{s} OK" in r.stdout
