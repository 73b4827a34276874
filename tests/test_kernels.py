"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes kernel bodies on CPU; TPU is the target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import accel_weights
from repro.graph import Graph, WebGraphSpec, generate_webgraph, to_bsr
from repro.kernels import (DeviceBSR, bsr_matvec, hits_sweep_bsr,
                           pad_empty_rows)
from repro.kernels.ref import bsr_scaled_matvec_ref
from repro.sparse.spmv import spmv_dst


def _graph(n, e, seed, dangling=0.4):
    return generate_webgraph(WebGraphSpec(n, e, dangling, seed=seed))


@pytest.mark.parametrize("bs", [8, 32, 128])
@pytest.mark.parametrize("v", [1, 4, 8])
def test_bsr_matvec_shapes(bs, v):
    g = _graph(300, 2500, seed=bs * 10 + v)
    lt = DeviceBSR.build(g, bs=bs, transpose=True)
    key = jax.random.key(v)
    x = jax.random.uniform(key, (g.n_nodes, v) if v > 1 else (g.n_nodes,),
                           jnp.float32)
    ch = jnp.asarray(accel_weights(g.indeg(), g.outdeg())[1], jnp.float32)
    y = bsr_matvec(lt, x, ch)
    xs = x * (ch[:, None] if v > 1 else ch)
    y_ref = spmv_dst(xs, jnp.asarray(g.src), jnp.asarray(g.dst), g.n_nodes)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-4),
                                        (jnp.bfloat16, 5e-2)])
def test_bsr_matvec_dtypes(dtype, rtol):
    g = _graph(256, 2000, seed=7)
    lt = DeviceBSR.build(g, bs=64, transpose=True, dtype=dtype)
    x = jax.random.uniform(jax.random.key(0), (g.n_nodes, 4), dtype)
    y = bsr_matvec(lt, x)
    y_ref = spmv_dst(x.astype(jnp.float32), jnp.asarray(g.src),
                     jnp.asarray(g.dst), g.n_nodes)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref),
                               rtol=rtol, atol=rtol * 10)


def test_bsr_vs_dense_oracle():
    g = _graph(200, 1500, seed=3)
    bsr = pad_empty_rows(to_bsr(g.reverse(), 32))
    idx = np.stack([bsr.brow, bsr.bcol], 1).astype(np.int32)
    x = jax.random.uniform(jax.random.key(1), (bsr.n_padded, 4), jnp.float32)
    cin = jax.random.uniform(jax.random.key(2), (bsr.n_padded, 1), jnp.float32)
    from repro.kernels.bsr_spmm import bsr_scaled_matvec
    y = bsr_scaled_matvec(jnp.asarray(bsr.blocks), jnp.asarray(idx), x, cin,
                          bs=32)
    y_ref = bsr_scaled_matvec_ref(jnp.asarray(bsr.blocks), jnp.asarray(idx),
                                  x, cin, bsr.n_padded)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5,
                               atol=1e-5)


def test_bsr_empty_rows_written():
    """Graphs with empty block rows must still zero those output tiles."""
    g = Graph(100, np.array([0, 1], np.int32), np.array([99, 98], np.int32))
    lt = DeviceBSR.build(g, bs=16, transpose=True)
    x = jnp.ones((100,), jnp.float32)
    y = bsr_matvec(lt, x)
    y_ref = spmv_dst(x, jnp.asarray(g.src), jnp.asarray(g.dst), 100)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref))


# ------------------------------------------- multi-column RHS (serve path)


def _bsr_with_tail(n, e, bs, seed, transpose=True):
    """A BSR whose last block-row is a partial tail (n % bs != 0)."""
    assert n % bs != 0
    g = _graph(n, e, seed=seed)
    gg = g.reverse() if transpose else g
    return g, pad_empty_rows(to_bsr(gg, bs))


@pytest.mark.parametrize("v", [2, 8])
def test_bsr_multicol_per_column_cin(v):
    """cin with V columns: each output column uses its own diagonal — the
    serve backend's per-query induced weights fused into the kernel."""
    from repro.kernels.bsr_spmm import bsr_scaled_matvec
    g, bsr = _bsr_with_tail(210, 1700, 32, seed=5)
    idx = np.stack([bsr.brow, bsr.bcol], 1).astype(np.int32)
    x = jax.random.uniform(jax.random.key(1), (bsr.n_padded, v), jnp.float32)
    cin = jax.random.uniform(jax.random.key(2), (bsr.n_padded, v),
                             jnp.float32)
    y = bsr_scaled_matvec(jnp.asarray(bsr.blocks), jnp.asarray(idx), x, cin,
                          bs=32)
    y_ref = bsr_scaled_matvec_ref(jnp.asarray(bsr.blocks), jnp.asarray(idx),
                                  x, cin, bsr.n_padded)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5,
                               atol=1e-5)
    # and column j == the single-column call with its own cin
    for j in [0, v - 1]:
        y_j = bsr_scaled_matvec(jnp.asarray(bsr.blocks), jnp.asarray(idx),
                                x[:, j:j + 1], cin[:, j:j + 1], bs=32)
        np.testing.assert_allclose(np.asarray(y)[:, j],
                                   np.asarray(y_j)[:, 0], rtol=1e-5,
                                   atol=1e-6)


def test_bsr_multicol_uneven_tail_and_empty_blocks():
    """Partial tail block-row + fully empty block-rows, multi-column RHS:
    pad rows must come back exactly zero and real rows must match the
    edge-list oracle."""
    # two edges at the graph's corners leave most block-rows empty
    g = Graph(100, np.array([0, 1], np.int32), np.array([99, 98], np.int32))
    lt = DeviceBSR.build(g, bs=16, transpose=True)
    assert lt.n_pad > g.n_nodes  # uneven tail: 100 pads to 112
    x = jax.random.uniform(jax.random.key(3), (100, 4), jnp.float32)
    cin = jax.random.uniform(jax.random.key(4), (100, 4), jnp.float32)
    y = bsr_matvec(lt, x, cin)
    y_ref = spmv_dst(x * cin, jnp.asarray(g.src), jnp.asarray(g.dst), 100)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-6,
                               atol=1e-6)


def test_bsr_multicol_accum_float64():
    """float64 accumulation: ill-conditioned columns (big + tiny entries)
    must come out at f64 precision, matching a numpy dense oracle."""
    g, bsr = _bsr_with_tail(150, 1200, 64, seed=9)
    idx = np.stack([bsr.brow, bsr.bcol], 1).astype(np.int32)
    rng = np.random.default_rng(0)
    x = rng.random((bsr.n_padded, 4)) * np.array([1.0, 1e-9, 1e9, 1.0])
    cin = rng.random((bsr.n_padded, 4))
    from repro.kernels.bsr_spmm import bsr_scaled_matvec
    y = bsr_scaled_matvec(jnp.asarray(bsr.blocks, jnp.float64),
                          jnp.asarray(idx), jnp.asarray(x),
                          jnp.asarray(cin), bs=64,
                          accum_dtype=jnp.float64)
    dense = np.asarray(bsr.to_dense(), np.float64)
    pad = bsr.n_padded - dense.shape[0]
    dense = np.pad(dense, ((0, pad), (0, pad)))
    y_ref = dense @ (x * cin)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-13, atol=1e-13)


def test_bsr_float64_edge_values_not_quantized():
    """Per-edge float64 weights must reach the blocks at full precision
    (no f32 intermediate inside to_bsr) — the serve backends' <=1e-10
    parity depends on it for weighted sweeps."""
    g = _graph(180, 1400, seed=17)
    rng = np.random.default_rng(1)
    w = rng.random(g.n_edges)  # generic f64 values, not representable in f32
    lt = DeviceBSR.build(g, bs=32, transpose=True, dtype=jnp.float64,
                         values=w)
    assert lt.blocks.dtype == jnp.float64
    x = jnp.asarray(rng.random((g.n_nodes, 3)))
    y = bsr_matvec(lt, x, accum_dtype=jnp.float64)
    y_ref = spmv_dst(x, jnp.asarray(g.src), jnp.asarray(g.dst), g.n_nodes,
                     jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-13,
                               atol=1e-14)


@pytest.mark.parametrize("interpret", [True, False])
def test_bsr_multicol_interpret_modes_agree(interpret):
    """interpret=True/False must agree with the ref oracle (the compiled
    Mosaic path only lowers on TPU — skipped elsewhere)."""
    if not interpret and jax.default_backend() != "tpu":
        pytest.skip("compiled Pallas path needs a TPU backend")
    from repro.kernels.bsr_spmm import bsr_scaled_matvec
    g, bsr = _bsr_with_tail(140, 1100, 32, seed=13)
    idx = np.stack([bsr.brow, bsr.bcol], 1).astype(np.int32)
    x = jax.random.uniform(jax.random.key(5), (bsr.n_padded, 8), jnp.float32)
    cin = jax.random.uniform(jax.random.key(6), (bsr.n_padded, 8),
                             jnp.float32)
    y = bsr_scaled_matvec(jnp.asarray(bsr.blocks), jnp.asarray(idx), x, cin,
                          bs=32, interpret=interpret)
    y_ref = bsr_scaled_matvec_ref(jnp.asarray(bsr.blocks), jnp.asarray(idx),
                                  x, cin, bsr.n_padded)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5,
                               atol=1e-5)


# -------------------------------------------- interpret-mode default (lib)


def test_interpret_default_is_opt_in(monkeypatch):
    """Library default must be compiled Pallas wherever Mosaic lowers (TPU)
    and interpreter elsewhere — never a hardcoded interpret=True — with the
    env var as the explicit override."""
    from repro.kernels.bsr_spmm import resolve_interpret
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    # auto tracks the platform: non-TPU hosts interpret, TPU compiles
    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False  # the regression: was True
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_interpret(None) is True
    # explicit argument and env var both win over auto
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_interpret(None) is False
    # empty string means unset (the `VAR= cmd` shell idiom) -> auto
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "")
    assert resolve_interpret(None) is True


def test_bsr_default_interpret_runs_on_cpu():
    """Callers passing no interpret flag must still work on CPU hosts (the
    auto default resolves to the interpreter off-TPU)."""
    g = _graph(120, 900, seed=21)
    lt = DeviceBSR.build(g, bs=32, transpose=True)
    x = jax.random.uniform(jax.random.key(7), (g.n_nodes, 4), jnp.float32)
    y = bsr_matvec(lt, x)  # no interpret argument anywhere
    y_ref = spmv_dst(x, jnp.asarray(g.src), jnp.asarray(g.dst), g.n_nodes)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5,
                               atol=1e-5)


def test_hits_sweep_bsr_full_convergence():
    """Kernel-path accelerated HITS converges to the segment-sum result."""
    from repro.core import accel_hits
    g = _graph(500, 4000, seed=11)
    ca, ch = accel_weights(g.indeg(), g.outdeg())
    sweep, _, _ = hits_sweep_bsr(g, ca, ch, bs=128)
    h = jnp.full((g.n_nodes,), 1.0 / g.n_nodes, jnp.float32)
    for _ in range(30):
        h, a = sweep(h)
    ref = accel_hits(g, tol=1e-12)
    assert np.abs(np.asarray(h, np.float64) - ref.v).max() < 1e-4
