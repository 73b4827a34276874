"""Sweep-backend parity harness + serve-path property tests.

Parity: every backend (dense / sharded x {replicated, dual_blocked} x
1/2/4/8 host devices / bsr) must reproduce the single-device RankService
oracle to <=1e-10 L1 on the same queries, through the cold, cache-hit, and
warm-start (refresh) paths. Sharded runs in a subprocess with
``--xla_force_host_platform_device_count=8`` (as in test_dist.py).

Properties (via tests/_hypothesis_fallback.py on bare environments):
``hits_sweep_cols`` column independence and ``graph.subgraph`` base-set
expansion invariants.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=600)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    return r.stdout


_PARITY_PRELUDE = r"""
import numpy as np, jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from repro.graph import WebGraphSpec, generate_webgraph
from repro.serve import RankService, RankServiceConfig

TOL = 1e-12
g = generate_webgraph(WebGraphSpec(260, 2000, 0.5, seed=2))
rng = np.random.default_rng(0)
queries = [rng.choice(g.n_nodes, size=4, replace=False) for _ in range(4)]

oracle = RankService(g, RankServiceConfig(v_max=4, tol=TOL))
ref_cold = oracle.rank(queries)
ref_warm = oracle.rank(queries, refresh=True)

def check(label, **kw):
    svc = RankService(g, RankServiceConfig(v_max=4, tol=TOL, **kw))
    cold = svc.rank(queries)
    for r, o in zip(cold, ref_cold):
        assert r.status == "cold", (label, r.status)
        assert (r.nodes == o.nodes).all(), label
        assert np.abs(r.authority - o.authority).sum() <= 1e-10, label
        assert np.abs(r.hub - o.hub).sum() <= 1e-10, label
        # every cold result ships a residual certificate <= the polish tol
        assert r.residual is not None and r.residual <= TOL, \
            (label, r.residual)
    hits = svc.rank(queries)           # cache-hit path: bit-identical
    for r2, r in zip(hits, cold):
        assert r2.status == "hit" and r2.iters == 0, (label, r2.status)
        assert np.array_equal(r2.authority, r.authority), label
        assert np.array_equal(r2.hub, r.hub), label
    warm = svc.rank(queries, refresh=True)   # warm-start path
    for r3, c3, o in zip(warm, cold, ref_warm):
        assert r3.status == "warm", (label, r3.status)
        assert r3.iters <= c3.iters, (label, r3.iters, c3.iters)
        assert np.abs(r3.authority - o.authority).sum() <= 1e-10, label
        assert np.abs(r3.hub - o.hub).sum() <= 1e-10, label
    return svc
"""

PARITY_SHARDED = _PARITY_PRELUDE + r"""
assert len(jax.devices()) == 8, jax.devices()
# 3 devices: non-power-of-two counts must work too (blocked layouts pad)
for s in (1, 2, 3, 4, 8):
    svc = check(f"sharded/{MODE}/{s}", backend="sharded", shard_mode=MODE,
                shard_devices=s)
    assert set(svc.stats["backend_batches"]) == {"sharded"}
print("SHARDED", MODE, "OK")
"""

PARITY_LOCAL = _PARITY_PRELUDE + r"""
svc = check("bsr", backend="bsr")
assert set(svc.stats["backend_batches"]) == {"bsr"}
check("dense", backend="dense")
# auto resolves to a real backend and stays correct on 8 host devices
svc = check("auto", backend="auto")
assert set(svc.stats["backend_batches"]) <= {"dense", "sharded", "bsr"}
print("LOCAL OK")
"""

PARITY_LADDER = _PARITY_PRELUDE + r"""
assert len(jax.devices()) == 8, jax.devices()
# precision-ladder axis (ISSUE 7): bulk sweeps at a lower dtype + f64
# polish must land on the same fixed point as the single-phase f64 oracle,
# on every backend and device count, with a certificate <= tol.
for sd in ("bfloat16", "float32", "float64"):
    for s in (1, 2, 4, 8):
        svc = check(f"ladder/{MODE}/{sd}/{s}", backend="sharded",
                    shard_mode=MODE, shard_devices=s, sweep_dtype=sd)
        assert set(svc.stats["backend_batches"]) == {"sharded"}
    if MODE == "replicated":  # local backends once, not per shard mode
        check(f"ladder/dense/{sd}", backend="dense", sweep_dtype=sd)
        check(f"ladder/bsr/{sd}", backend="bsr", sweep_dtype=sd)
# a degenerate f64 ladder is normalized to single-phase: bit-identical
svc64 = RankService(g, RankServiceConfig(v_max=4, tol=TOL,
                                         sweep_dtype="float64"))
for r, o in zip(svc64.rank(queries), ref_cold):
    assert np.array_equal(r.authority, o.authority)
    assert np.array_equal(r.hub, o.hub)
print("LADDER_PARITY", MODE, "OK")
"""

PARITY_LUMPED = _PARITY_PRELUDE + r"""
assert len(jax.devices()) == 8, jax.devices()
# plan-time lumping axis (ISSUE 10): lumping="on" must land on the same
# fixed point as the unlumped f64 oracle on every backend and device
# count — the reduced sweep + exact unlump is invisible to clients.
def check_lumped(label, **kw):
    svc = RankService(g, RankServiceConfig(v_max=4, tol=TOL, lumping="on",
                                           **kw))
    for r, o in zip(svc.rank(queries), ref_cold):
        assert (r.nodes == o.nodes).all(), label
        assert np.abs(r.authority - o.authority).sum() <= 1e-10, label
        assert np.abs(r.hub - o.hub).sum() <= 1e-10, label
    hits = svc.rank(queries)   # lumped plans serve bit-identical repeats
    for r2 in hits:
        assert r2.status == "hit" and r2.iters == 0, (label, r2.status)
    return svc

for mode in ("replicated", "dual_blocked"):
    for s in (1, 2, 4, 8):
        check_lumped(f"lumped/sharded/{mode}/{s}", backend="sharded",
                     shard_mode=mode, shard_devices=s)
check_lumped("lumped/dense", backend="dense")
check_lumped("lumped/bsr", backend="bsr")
print("LUMPED_PARITY OK")
"""

LADDER = r"""
import numpy as np, jax
jax.config.update("jax_enable_x64", True)
from repro.graph import WebGraphSpec, generate_webgraph
from repro.serve.backends import ShardedSweepBackend

g = generate_webgraph(WebGraphSpec(200, 1500, 0.5, seed=1))
n_pad, v, s = 256, 4, 8
w = np.ones(g.n_edges)
measured = {}
for mode in ("replicated", "dual_blocked"):
    be = ShardedSweepBackend(mode=mode, n_devices=s)
    meas = be.measure_wire_bytes(n_pad, v, g.src, g.dst, w)
    analytic = be.collective_bytes_per_sweep(n_pad, v)
    measured[mode] = meas
    print(f"{mode}: measured_wire={meas} analytic={analytic}")
    assert meas > 0, mode
    # the HLO holds the collectives the analytic model counts, each once
    # (plus the L1 norm's few-byte psum)
    assert abs(meas - analytic) <= 0.01 * analytic, (mode, meas, analytic)
# the dist ladder, measured from compiled HLO: blocked moves fewer bytes
assert measured["dual_blocked"] <= measured["replicated"], measured
print("LADDER OK")
"""


@pytest.mark.parametrize("name,code", [
    ("sharded_replicated", "MODE='replicated'\n" + PARITY_SHARDED),
    ("sharded_dual_blocked", "MODE='dual_blocked'\n" + PARITY_SHARDED),
    ("local_backends", PARITY_LOCAL),
    ("collective_ladder", LADDER),
    ("precision_ladder_replicated",
     "MODE='replicated'\n" + PARITY_LADDER),
    ("precision_ladder_dual_blocked",
     "MODE='dual_blocked'\n" + PARITY_LADDER),
    ("lumped_parity", PARITY_LUMPED),
])
def test_backend_parity(name, code):
    out = _run(code)
    assert "OK" in out


# -------------------------------------------------- auto heuristic (unit)


def test_select_backend_heuristic():
    from repro.serve import select_backend
    f32 = np.float32
    # multi-device + big union subgraph -> sharded, regardless of pallas
    assert select_backend(4096, 80000, n_devices=8,
                          pallas_compiled=False, dtype=f32) == "sharded"
    # single device, dense-block regime, compiled pallas -> bsr
    assert select_backend(256, 4000, n_devices=1,
                          pallas_compiled=True, dtype=f32) == "bsr"
    # ... but never at f64: Mosaic has no f64, so the sweep stays dense
    assert select_backend(256, 4000, n_devices=1, pallas_compiled=True,
                          dtype=np.float64) == "dense"
    # interpreter-mode pallas never wins over XLA dense
    assert select_backend(256, 4000, n_devices=1,
                          pallas_compiled=False, dtype=f32) == "dense"
    # small/sparse subgraphs stay dense even on a mesh
    assert select_backend(64, 200, n_devices=8,
                          pallas_compiled=True, dtype=f32) == "dense"


def test_unknown_backend_rejected():
    from repro.graph import Graph
    from repro.serve import RankService, RankServiceConfig, make_backend
    g = Graph(4, np.array([0, 1], np.int32), np.array([1, 2], np.int32))
    with pytest.raises(ValueError):
        RankService(g, RankServiceConfig(backend="gpu-magic"))
    with pytest.raises(ValueError):
        make_backend("gpu-magic")
    with pytest.raises(ValueError):
        make_backend("sharded", shard_mode="tri_blocked")


# -------------------------------------- hits_sweep_cols column properties


@given(st.integers(0, 10**6), st.integers(1, 8), st.integers(10, 40))
@settings(max_examples=15, deadline=None)
def test_sweep_cols_column_independence(seed, v, n):
    """Each column of the batched sweep equals the corresponding
    single-query induced sweep: per-column masks + induced weights make
    column j exactly P_j.L.P_j, independent of what its neighbors rank."""
    import jax.numpy as jnp

    from repro.core.hits import EdgeList, hits_sweep_cols
    from repro.core.weights import accel_weights

    rng = np.random.default_rng(seed)
    e = int(rng.integers(n, 4 * n))
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    masks = (rng.random((n, v)) < rng.uniform(0.3, 0.9)).astype(float)
    ca = np.zeros((n, v))
    ch = np.zeros((n, v))
    for j in range(v):
        m = masks[:, j]
        sel = (m[src] > 0) & (m[dst] > 0)
        indeg = np.bincount(dst[sel], minlength=n)
        outdeg = np.bincount(src[sel], minlength=n)
        ca_j, ch_j = accel_weights(indeg, outdeg)
        ca[:, j] = ca_j * m
        ch[:, j] = ch_j * m
    edges = EdgeList(jnp.asarray(src), jnp.asarray(dst), n,
                     jnp.ones(e, jnp.float64))
    h0 = rng.random((n, v)) * masks
    sweep = hits_sweep_cols(edges, jnp.asarray(ca), jnp.asarray(ch),
                            jnp.asarray(masks))
    h_all, a_all = sweep(jnp.asarray(h0))
    for j in range(v):
        sweep_j = hits_sweep_cols(edges, jnp.asarray(ca[:, j:j + 1]),
                                  jnp.asarray(ch[:, j:j + 1]),
                                  jnp.asarray(masks[:, j:j + 1]))
        h_j, a_j = sweep_j(jnp.asarray(h0[:, j:j + 1]))
        assert np.abs(np.asarray(h_all)[:, j]
                      - np.asarray(h_j)[:, 0]).max() < 1e-12
        assert np.abs(np.asarray(a_all)[:, j]
                      - np.asarray(a_j)[:, 0]).max() < 1e-12


# ------------------------------------------- subgraph expansion invariants


@given(st.integers(0, 10**6), st.integers(1, 6),
       st.integers(1, 16), st.integers(1, 16))
@settings(max_examples=15, deadline=None)
def test_base_set_expansion_invariants(seed, n_roots, out_cap, in_cap):
    """root set ⊆ base set; expansion is deterministic; and the base set is
    bounded by b: |base| <= R + R*out_cap + R*in_cap (the Kleinberg cap)."""
    from repro.graph import SubgraphExtractor, WebGraphSpec, generate_webgraph

    rng = np.random.default_rng(seed)
    g = generate_webgraph(WebGraphSpec(150, 900, 0.4,
                                       seed=int(rng.integers(1 << 30))))
    roots = rng.choice(g.n_nodes, size=n_roots, replace=False)
    ex = SubgraphExtractor(g, out_cap=out_cap, in_cap=in_cap)
    base = ex.expand(roots)
    assert set(roots.tolist()) <= set(base.tolist())
    assert (np.diff(base) > 0).all()  # sorted unique
    assert len(base) <= n_roots * (1 + out_cap + in_cap)
    again = ex.expand(np.array(list(reversed(roots.tolist()))))
    assert np.array_equal(base, again)  # deterministic, order-insensitive
    fs = ex.extract(roots)
    assert np.array_equal(fs.nodes, base.astype(np.int32))
    assert np.array_equal(ex.extract(roots).nodes, fs.nodes)
