"""Compile the serve path's device programs, and the whole-graph engine's
pass, for a described TPU v5e, with no chip attached.

The TPU compiler is installed with libtpu, and it compiles for a topology
that is described rather than attached, so these tests catch what the
Pallas interpreter cannot: Mosaic refusing a kernel (i64 block indices
under ``jax_enable_x64``, which conftest turns on as every entry point
does), scalar tables that overflow SMEM, and programs that do not fit the
chip's memory. Shapes are the serve path's own at the sizes
``chip_smoke.py`` drives: bs=128 blocks, V=8 columns, unions up to
n_pad=2^18.

The topology is described inside a fixture, never at import: only one
process may load libtpu at a time, and every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import bsr_spmm
from repro.serve import backends

BS, V = 128, 8
N_PAD, E_PAD = 1 << 18, 1 << 21   # Kleinberg-size unions (~160k nodes)
SMALL = (1 << 12, 1 << 15)        # launcher-default unions (5 roots, caps 32)
N_BLOCKS = 4096          # nonzero 128-blocks of a dense (bsr-routed) union
BSR_N_PAD = 1 << 14


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("data",))


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_ok(compiled, kernel):
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == kernel
    return compiled


# ------------------------------------------------------------- Pallas BSR


@pytest.mark.parametrize("block_dtype", ["float32", "bfloat16"])
def test_bsr_matvec_compiles_under_x64(one_chip, block_dtype):
    """The block kernel, f32 blocks and the ladder's bf16 blocks, both with
    f32 accumulation."""
    assert jax.config.jax_enable_x64
    s = lambda shape, dt: _shape(one_chip, shape, dt)  # noqa: E731
    bd = jnp.dtype(block_dtype)
    compiled = bsr_spmm._bsr_scaled_matvec.lower(
        s((N_BLOCKS, BS, BS), bd), s((N_BLOCKS, 2), jnp.int32),
        s((BSR_N_PAD, V), bd), s((BSR_N_PAD, V), bd), bs=BS,
        interpret=False, accum_dtype=jnp.float32).compile()
    _compiled_ok(compiled, kernel=True)


def test_bsr_block_table_fits_smem_at_n_pad_2_18(one_chip):
    """A Kleinberg-size union's block table (2^15 blocks) fits the scalar
    memory: the table is prefetched flat, not as a lane-padded 2-D array."""
    s = lambda shape, dt: _shape(one_chip, shape, dt)  # noqa: E731
    nb = 1 << 15
    compiled = bsr_spmm._bsr_scaled_matvec.lower(
        s((nb, BS, BS), jnp.bfloat16), s((nb, 2), jnp.int32),
        s((N_PAD, V), jnp.bfloat16), s((N_PAD, V), jnp.bfloat16), bs=BS,
        interpret=False, accum_dtype=jnp.float32).compile()
    _compiled_ok(compiled, kernel=True)


@pytest.mark.parametrize("ladder", [None, "bfloat16"])
def test_bsr_fused_loop_compiles_under_x64(one_chip, ladder):
    """The fused on-device loop (``bsr_converge_cols``) in f32, alone and
    behind the bf16 bulk phase."""
    s = lambda shape, dt: _shape(one_chip, shape, dt)  # noqa: E731
    f32 = jnp.float32
    blocks = s((N_BLOCKS, BS, BS), f32)
    idx = s((N_BLOCKS, 2), jnp.int32)
    vec = s((BSR_N_PAD, V), f32)
    lo = None if ladder is None else s((N_BLOCKS, BS, BS), jnp.bfloat16)
    compiled = bsr_spmm.bsr_converge_cols.lower(
        blocks, idx, blocks, idx, vec, vec, vec, vec, s((), f32), bs=BS,
        interpret=False, accum_dtype=f32, max_iter=1000,
        lt_blocks_lo=lo, l_blocks_lo=lo, bulk_tol=1e-3,
        bulk_dtype=ladder).compile()
    _compiled_ok(compiled, kernel=True)


# ------------------------------------------------------ whole-graph engine


@pytest.mark.parametrize("dtype", ["float64", "float32"])
def test_engine_segment_sum_compiles_without_scatter(one_chip, dtype):
    """One shard's pass of the whole-graph engine at the stanford
    back-button graph's size (225,441 pages, 4,303,868 edges in 8 shards),
    in the engine's dtype and the control's: a gather and a scan, with no
    scatter left in the program."""
    from repro.core import engine
    s = lambda shape, dt: _shape(one_chip, shape, dt)  # noqa: E731
    n, rows = 225441, 4208  # 537,984 edges in 128 columns
    dt, i32 = jnp.dtype(dtype), jnp.int32
    compiled = engine._segment_sum.lower(
        s((n,), dt), s((rows, 128), i32), s((rows, 128), dt),
        s((rows, 128), jnp.bool_), s((n,), i32), s((n,), jnp.bool_)).compile()
    _compiled_ok(compiled, kernel=False)
    assert not re.search(r"\bscatter\(", compiled.as_text())  # an HLO op


# ------------------------------------------------------- dense and sharded


def _sorted_edges(sharding, n_pad, e_pad, dtype):
    from repro.sparse.segsum import SortedEdges
    i32, b = jnp.int32, jnp.bool_
    return SortedEdges(*(_shape(sharding, shape, dt) for shape, dt in (
        ((e_pad,), i32), ((e_pad,), dtype), ((e_pad,), b), ((n_pad,), i32),
        ((n_pad,), b), ((e_pad,), i32))))


@pytest.mark.parametrize("shape,rank_k,ladder", [
    ((N_PAD, E_PAD), 0, None), (SMALL, 10, None), (SMALL, 0, "bfloat16"),
    (SMALL, 0, "float32")])
def test_dense_f64_loop_compiles(one_chip, shape, rank_k, ladder):
    """The dense f64 convergence loop: at a Kleinberg-size union as the
    service runs it by default (no Mosaic anywhere), and at a
    launcher-default union with ``lax.top_k`` on f64 for the rank-stable
    exit, or with the precision ladder's low-precision bulk phase. Each
    pass is a gather and a scan over the plan's sorted edges, with no
    scatter left in the program."""
    s = lambda shp, dt: _shape(one_chip, shp, dt)  # noqa: E731
    f64 = jnp.float64
    n_pad, e_pad = shape
    vec = s((n_pad, V), f64)
    edges = _sorted_edges(one_chip, n_pad, e_pad, f64)
    compiled = backends._converge_batch.lower(
        vec, edges, edges, vec, vec, vec, s((), f64), 1000, rank_k=rank_k,
        bulk_dtype=ladder, bulk_tol=1e-3).compile()
    _compiled_ok(compiled, kernel=False)
    assert not re.search(r"\bscatter\(", compiled.as_text())  # an HLO op
    mem = compiled.memory_analysis()
    # fits one 16 GB chip with room for the plan cache
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 4 << 30, total


@pytest.mark.parametrize("mode", ["replicated", "dual_blocked"])
def test_sharded_loop_compiles_on_4_chips(mesh, mode):
    """The sharded f64 sweep over a 4-chip mesh: edge planes split across
    the chips, collectives in the program, and a per-chip footprint a
    quarter of the edge arrays."""
    s_n = 4
    per = E_PAD // s_n
    f64, i32 = jnp.float64, jnp.int32
    edges = NamedSharding(mesh, P("data", None))
    rep = NamedSharding(mesh, P())
    fn = backends._sharded_converge(mesh, mode, N_PAD, per, V, 1000, f64,
                                    ("data",))
    vec = _shape(rep, (N_PAD, V), f64)
    if mode == "replicated":
        h0 = vec
        eargs = (_shape(edges, (s_n, per), i32),) * 2 + \
            (_shape(edges, (s_n, per), f64),)
    else:
        nb = N_PAD // s_n
        h0 = _shape(NamedSharding(mesh, P("data", None, None)),
                    (s_n, nb, V), f64)
        plane = (_shape(edges, (s_n, per), i32),) * 2 + \
            (_shape(edges, (s_n, per), f64),)
        eargs = plane * 2
    with jax.set_mesh(mesh):
        compiled = fn.lower(h0, vec, vec, vec, eargs, _shape(rep, (), f64),
                            _shape(rep, (), f64)).compile()
    text = _compiled_ok(compiled, kernel=False).as_text()
    # an f64 psum has no TPU all-reduce: it compiles to an all-gather
    assert "all-gather" in text or "all-reduce" in text
    mem = compiled.memory_analysis()
    # each chip holds the replicated (n_pad, V) vectors, a quarter of the
    # edge planes (not all of them) and two padded scalars
    vectors = 4 * N_PAD * V * 8
    full_edges = len(eargs) // 3 * E_PAD * (4 + 4 + 8)
    assert mem.argument_size_in_bytes <= vectors + full_edges / s_n + 4096
