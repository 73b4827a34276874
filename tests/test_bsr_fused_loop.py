"""Fused on-device BSR convergence (kernels.bsr_converge_cols) vs the
host-driven loop.

The fused path runs ``lax.while_loop`` around the Pallas sweep with the
tolerance check in the carry — one device dispatch per batch. The
host-driven loop (``BsrSweepBackend(fused=False)``) is the semantic
reference: both must agree on the fixed-point vectors (<=1e-10 L1) and the
per-column sweep counts (+-1), through max-iteration cutoffs and
already-converged warm starts, in interpret mode here
(``tests/test_tpu_compile.py`` compiles the same loop for the TPU).
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import assume, given, settings, strategies as st

from repro.core.weights import accel_weights
from repro.graph.structure import next_pow2
from repro.serve.backends import BsrSweepBackend, SweepBatch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_batch(seed, n, v, tol=1e-10, max_iter=200, h0=None):
    """A service-shaped padded batch: sentinel edges into the dead pad row,
    per-column random base-set masks with matching induced accel weights,
    uniform-over-support start vectors."""
    rng = np.random.default_rng(seed)
    n_pad = next_pow2(max(n + 1, 16))
    e = int(rng.integers(2 * n, 6 * n))
    e_pad = next_pow2(max(e, 16))
    src = np.full(e_pad, n_pad - 1, np.int32)
    dst = np.full(e_pad, n_pad - 1, np.int32)
    w = np.zeros(e_pad)
    src[:e] = rng.integers(0, n, e)
    dst[:e] = rng.integers(0, n, e)
    w[:e] = 1.0
    ca = np.zeros((n_pad, v))
    ch = np.zeros((n_pad, v))
    mask = np.zeros((n_pad, v))
    got_h0 = h0 is not None
    h0 = np.asarray(h0) if got_h0 else np.zeros((n_pad, v))
    for j in range(v):
        m = np.zeros(n_pad)
        members = rng.choice(n, size=max(4, n // 2), replace=False)
        m[members] = 1.0
        sel = (m[src] > 0) & (m[dst] > 0) & (w > 0)
        indeg = np.bincount(dst[sel], minlength=n_pad)
        outdeg = np.bincount(src[sel], minlength=n_pad)
        ca_j, ch_j = accel_weights(indeg, outdeg)
        ca[:, j] = ca_j * m
        ch[:, j] = ch_j * m
        mask[:, j] = m
        if not got_h0:
            h0[:, j] = m / m.sum()
    return SweepBatch(h0=h0, src=src, dst=dst, w=w, ca=ca, ch=ch, mask=mask,
                      tol=tol, max_iter=max_iter, dtype=jnp.float64)


def spectral_ratios(batch):
    """Per column, |lambda_2| / lambda_1 of the masked one-sweep operator
    L·diag(ca)·Lᵀ·diag(ch): the rate at which power iteration sheds
    everything but the fixed point."""
    n = batch.h0.shape[0]
    adj = np.zeros((n, n))
    np.add.at(adj, (batch.src, batch.dst), batch.w)
    out = []
    for j in range(batch.h0.shape[1]):
        m = batch.mask[:, j]
        lm = adj * m[:, None] * m[None, :]
        op = lm @ (batch.ca[:, j, None] * lm.T) * batch.ch[None, :, j]
        ev = np.sort(np.abs(np.linalg.eigvals(op)))[::-1]
        out.append(ev[1] / ev[0])
    return np.array(out)


def fused_and_host(batch, bs=32):
    fused = BsrSweepBackend(bs=bs, fused=True).converge(batch)
    host = BsrSweepBackend(bs=bs, fused=False).converge(batch)
    return fused, host


def assert_agree(fused, host, iter_slack=1):
    hf, af, cf = fused[:3]
    hh, ah, ch_ = host[:3]
    assert np.abs(hf - hh).sum() <= 1e-10
    assert np.abs(af - ah).sum() <= 1e-10
    assert np.abs(cf.astype(int) - ch_.astype(int)).max() <= iter_slack


# ------------------------------------------------------- parity (property)


@given(st.integers(0, 10**6), st.integers(1, 6), st.integers(24, 90))
@settings(max_examples=8, deadline=None)
def test_fused_matches_host_loop(seed, v, n):
    """Fixed-point vectors <=1e-10 L1 apart, sweep counts within +-1, on
    random graphs x random column masks; every well-posed column converges
    within max_iter."""
    batch = make_batch(seed, n, v)
    fused, host = fused_and_host(batch)
    assert_agree(fused, host)
    # well-posed: each column's spectral ratio lets max_iter sweeps shrink
    # the error well below tol. A draw with two near-equal dominant
    # components (the paper: the fixed point need not be unique, and close
    # to that it is slow) is checked for agreement above and then set aside
    rate = spectral_ratios(batch) ** batch.max_iter
    assume((rate <= batch.tol / 100).all())
    assert (fused[2] < batch.max_iter).all()


def test_fused_through_rank_service_matches_dense():
    """End-to-end: the default (fused) bsr backend serves the same scores
    as the dense oracle through RankService."""
    from repro.graph import WebGraphSpec, generate_webgraph
    from repro.serve import RankService, RankServiceConfig

    g = generate_webgraph(WebGraphSpec(260, 2000, 0.5, seed=2))
    rng = np.random.default_rng(0)
    queries = [rng.choice(g.n_nodes, size=4, replace=False) for _ in range(4)]
    ref = RankService(g, RankServiceConfig(v_max=4, tol=1e-12)).rank(queries)
    svc = RankService(g, RankServiceConfig(v_max=4, tol=1e-12, backend="bsr"))
    assert svc.cfg.bsr_fused  # fused is the default
    for r, o in zip(svc.rank(queries), ref):
        assert np.abs(r.authority - o.authority).sum() <= 1e-10
        assert np.abs(r.hub - o.hub).sum() <= 1e-10
        assert r.iters == o.iters


# ----------------------------------------------------- loop-boundary cases


def test_max_iter_cutoff():
    """An unreachable tolerance stops both loops at exactly max_iter, with
    identical (non-converged) vectors."""
    batch = make_batch(3, 60, 3, tol=1e-300, max_iter=7)
    fused, host = fused_and_host(batch)
    assert (fused[2] == 7).all() and (host[2] == 7).all()
    assert_agree(fused, host, iter_slack=0)


def test_zero_max_iter_returns_start_vector():
    """max_iter=0: no sweeps run; h is the start vector, conv==0, and the
    finalize half-step still produces a normalized authority."""
    batch = make_batch(4, 50, 2, max_iter=0)
    fused, host = fused_and_host(batch)
    assert (fused[2] == 0).all() and (host[2] == 0).all()
    assert np.array_equal(fused[0], batch.h0)
    assert_agree(fused, host, iter_slack=0)
    assert np.allclose(np.abs(fused[1]).sum(axis=0), 1.0)


def test_already_converged_warm_start_single_sweep():
    """Restarting from the converged fixed point hits tol on sweep 1 in
    both loops (the warm-start regime the vector cache serves)."""
    cold = make_batch(5, 70, 3, tol=1e-11)
    fused_cold, _ = fused_and_host(cold)
    h_star = fused_cold[0]
    warm = make_batch(5, 70, 3, tol=1e-11, h0=h_star)
    fused, host = fused_and_host(warm)
    assert (fused[2] == 1).all(), fused[2]
    assert (host[2] == 1).all(), host[2]
    assert_agree(fused, host, iter_slack=0)
    assert np.abs(fused[0] - h_star).sum() <= 1e-10


# ------------------------------------------------- dispatch-count evidence


def test_fused_loop_is_one_dispatch_per_batch(monkeypatch):
    """ISSUE 4 acceptance: the fused loop must not re-enter the Python
    kernel wrapper per iteration.

    After the first (tracing) call at a shape bucket, a repeat batch hits
    the jit cache: ZERO Python-level kernel invocations — the whole
    convergence loop is one device dispatch. The host-driven loop, by
    contrast, re-invokes the wrapper 2x per sweep (+1 finalize) because it
    syncs the residual to the host every iteration.
    """
    from repro.kernels import bsr_spmm, ops

    batch = make_batch(7, 60, 3)
    fused = BsrSweepBackend(bs=32, fused=True)
    host = BsrSweepBackend(bs=32, fused=False)
    fused.converge(batch)  # compile the bucket
    calls = {"fused": 0, "host": 0}

    real_inner = bsr_spmm._bsr_scaled_matvec

    def count_fused(*a, **kw):
        calls["fused"] += 1
        return real_inner(*a, **kw)

    # bsr_converge_cols resolves the kernel wrapper through module globals
    # at trace time; a cached jit executable never re-enters Python
    monkeypatch.setattr(bsr_spmm, "_bsr_scaled_matvec", count_fused)
    conv = fused.converge(batch)[2]
    assert calls["fused"] == 0, "fused loop re-entered Python per batch"

    real_outer = ops.bsr_scaled_matvec

    def count_host(*a, **kw):
        calls["host"] += 1
        return real_outer(*a, **kw)

    monkeypatch.setattr(ops, "bsr_scaled_matvec", count_host)
    host.converge(batch)
    iters = int(conv.max())
    assert iters >= 2
    # 2 wrapper calls per sweep + 1 finalize = per-iteration host syncs
    assert calls["host"] >= 2 * iters + 1


# --------------------------------------------- interpret / compiled modes


INTERPRET_ENV = r"""
import numpy as np, jax
jax.config.update("jax_enable_x64", True)
import sys, os
sys.path.insert(0, os.path.join(os.getcwd(), "tests"))
try:
    import hypothesis
except ImportError:
    from _hypothesis_fallback import install
    install()
from test_bsr_fused_loop import make_batch, fused_and_host, assert_agree

batch = make_batch(11, 64, 3)
fused, host = fused_and_host(batch)
assert_agree(fused, host)
print("ENV_MODE OK", os.environ.get("REPRO_PALLAS_INTERPRET", "<auto>"))
"""


@pytest.mark.parametrize("env_val", ["1", None])
def test_interpret_env_override_modes(env_val):
    """REPRO_PALLAS_INTERPRET must steer the fused loop exactly like the
    per-call kernels: forced-interpreter and auto mode both converge and
    agree with the host loop (the compiled mode is covered, without a
    chip, by tests/test_tpu_compile.py)."""
    env = dict(os.environ, PYTHONPATH="src")
    if env_val is None:
        env.pop("REPRO_PALLAS_INTERPRET", None)
    else:
        env["REPRO_PALLAS_INTERPRET"] = env_val
    r = subprocess.run([sys.executable, "-c", INTERPRET_ENV],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=600)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "ENV_MODE OK" in r.stdout


# ------------------------------------------- f64 never reaches compiled Pallas


def test_compiled_bsr_refuses_f64_at_construction():
    """Mosaic has no f64: an explicit bsr service at f64 with compiled
    Pallas is refused when it is built, and so is a bare backend's plan;
    f32 (with or without the bf16 ladder) and interpret mode still build."""
    from repro.graph import WebGraphSpec, generate_webgraph
    from repro.serve import RankService, RankServiceConfig

    g = generate_webgraph(WebGraphSpec(260, 2000, 0.5, seed=2))
    with pytest.raises(ValueError, match="float64"):
        RankService(g, RankServiceConfig(backend="bsr", interpret=False))
    RankService(g, RankServiceConfig(backend="bsr", interpret=False,
                                     dtype=jnp.float32, tol=1e-4))
    RankService(g, RankServiceConfig(backend="bsr", interpret=False,
                                     dtype=jnp.float32, tol=1e-4,
                                     sweep_dtype="bf16"))
    RankService(g, RankServiceConfig(backend="bsr", interpret=True))
    with pytest.raises(ValueError, match="float64"):
        BsrSweepBackend(bs=32, interpret=False).plan(make_batch(1, 40, 2))


def test_auto_keeps_f64_off_compiled_pallas():
    """``auto`` with compiled Pallas routes a dense-block f64 union to the
    dense backend, not to bsr, and serves it."""
    from repro.graph import WebGraphSpec, generate_webgraph
    from repro.serve import RankService, RankServiceConfig

    g = generate_webgraph(WebGraphSpec(200, 6000, 0.1, seed=3))
    svc = RankService(g, RankServiceConfig(v_max=4, backend="auto",
                                           interpret=False,
                                           shard_devices=1))
    rng = np.random.default_rng(0)
    svc.rank([rng.choice(g.n_nodes, size=4, replace=False)
              for _ in range(4)])
    assert svc.snapshot_stats()["backend_batches"] == {"dense": 1}
