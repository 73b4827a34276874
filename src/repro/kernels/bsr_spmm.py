"""Block-sparse (BSR) matrix × multi-vector Pallas TPU kernel with fused
diagonal scaling — the hot-path of the accelerated-HITS sweep.

Computes  y = A_bsr @ (x ⊙ cin)  where A is the (block-sparse) adjacency
matrix (or its transpose) and cin is the paper's Ch/Ca diagonal. The +2N
multiplies the paper accounts for (Table 2) are fused into the block
matmul's VMEM prologue — they never cost an HBM round trip.

TPU mapping: the grid walks the *nonzero blocks* sorted
by block-row; a scalar-prefetched (brow, bcol) table drives data-dependent
BlockSpec index maps (the canonical TPU block-sparse pattern). Consecutive
grid steps that share a block-row revisit the same output tile in VMEM, so
each y tile is written to HBM exactly once. Every block matmul is a dense
(bs × bs) × (bs × V) MXU op; bs defaults to 128 (MXU-aligned) and V ≥ 8
keeps the systolic array fed (multi-vector iteration).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def resolve_interpret(interpret=None) -> bool:
    """Resolve the Pallas interpret mode for library callers.

    Mosaic (interpret=False) only lowers on TPU, so the library default is
    *auto*: compiled on TPU, interpreter everywhere else. Explicit ``True``/
    ``False`` wins; the env var ``REPRO_PALLAS_INTERPRET`` (0/1) overrides
    the auto choice without touching call sites (CI / debugging knob).
    """
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "")
    if env:  # empty string == unset (the VAR= shell idiom): fall to auto
        return env.lower() not in ("0", "false")
    return jax.default_backend() != "tpu"


def _bsr_kernel(idx_ref, block_ref, x_ref, cin_ref, y_ref, *, accum_dtype):
    """One nonzero block per grid step.

    idx_ref: (2 * nblocks,) scalar-prefetched flat (brow, bcol) pairs.
    block_ref: (1, bs, bs) VMEM tile of A.
    x_ref:   (bs, V) VMEM tile of x rows for this block's columns.
    cin_ref: (bs, 1) VMEM tile of the scaling diagonal (same rows as x).
    y_ref:   (bs, V) VMEM output tile for this block's rows (revisited).
    """
    k = pl.program_id(0)
    brow_k = idx_ref[2 * k]
    brow_prev = idx_ref[2 * jnp.maximum(k - 1, 0)]
    is_first = jnp.logical_or(k == 0, brow_k != brow_prev)

    @pl.when(is_first)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    xs = (x_ref[...] * cin_ref[...]).astype(accum_dtype)
    blk = block_ref[0].astype(accum_dtype)
    # Mosaic's default contracts f32 operands in one bf16 pass (~1e-3
    # relative); f32 blocks ask for f32. bf16 blocks lose nothing at the
    # default, which keeps the ladder's bulk sweeps single-pass.
    precision = (None if block_ref.dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    y_ref[...] += jnp.dot(blk, xs, preferred_element_type=accum_dtype,
                          precision=precision).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "interpret", "accum_dtype"))
def _bsr_scaled_matvec(blocks, idx, x, cin, *, bs: int, interpret: bool,
                       accum_dtype):
    nblocks = blocks.shape[0]
    n_pad = x.shape[0]
    v = x.shape[1]
    cv = cin.shape[1]

    # block indices must be i32: a Python 0 traces as i64 under
    # jax_enable_x64, and Mosaic refuses an index map returning i64. The
    # zero is made inside each map (an index map may not capture one).
    def block_k(k, idx_ref):
        return k, jnp.int32(0), jnp.int32(0)

    def row_of(col):  # 0: brow, 1: bcol
        return lambda k, idx_ref: (idx_ref[2 * k + col], jnp.int32(0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, bs, bs), block_k),
            pl.BlockSpec((bs, v), row_of(1)),
            pl.BlockSpec((bs, cv), row_of(1)),
        ],
        out_specs=pl.BlockSpec((bs, v), row_of(0)),
    )
    # the table goes to SMEM flat: a 2-D (nblocks, 2) table pads its minor
    # dim to 128 lanes there, which caps it near 2k blocks
    return pl.pallas_call(
        functools.partial(_bsr_kernel, accum_dtype=accum_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, v), x.dtype),
        interpret=interpret,
    )(idx.reshape(-1), blocks, x, cin)


def bsr_scaled_matvec(blocks, idx, x, cin, *, bs: int,
                      interpret: bool | None = None,
                      accum_dtype=jnp.float32):
    """y[brow*bs:+bs] += blocks[k] @ (x ⊙ cin)[bcol*bs:+bs] over nonzero blocks.

    blocks: (nblocks, bs, bs); idx: (nblocks, 2) int32 (brow, bcol), sorted
    by brow with every block-row represented (pad empty rows via
    ops.pad_empty_rows); x: (n_pad, V); cin: (n_pad, 1) shared diagonal or
    (n_pad, V) per-column diagonals (the serve path's induced weights);
    returns (n_pad, V). ``interpret=None`` resolves via ``resolve_interpret``
    — compiled Pallas on TPU, interpreter elsewhere.
    """
    return _bsr_scaled_matvec(blocks, idx, x, cin, bs=bs,
                              interpret=resolve_interpret(interpret),
                              accum_dtype=accum_dtype)


# ------------------------------------------------- fused convergence loop


@functools.partial(jax.jit, static_argnames=("bs", "interpret", "accum_dtype",
                                             "max_iter", "rank_k",
                                             "stable_sweeps", "bulk_dtype"))
def bsr_converge_cols(lt_blocks, lt_idx, l_blocks, l_idx, h0, ca, ch, mask,
                      tol, *, bs: int, interpret: bool, accum_dtype,
                      max_iter: int, rank_k: int = 0, stable_sweeps: int = 2,
                      lt_blocks_lo=None, l_blocks_lo=None, bulk_tol=0.0,
                      bulk_dtype=None):
    """On-device masked multi-column accelerated-HITS convergence over two
    BSR operators: ``lax.while_loop`` around the Pallas sweep, tolerance
    check in the carry.

    The host-driven alternative round-trips per iteration (launch both
    half-step kernels, pull the residual to the host, decide); this runs
    the whole loop as ONE device dispatch per batch — the per-column L1
    residuals live in the carry, ``conv[j]`` records the sweep at which
    column j first hit ``tol`` (== the final sweep count when it never
    did), and all columns keep sweeping until the last converges
    (converged columns sit at their fixed point). ``tol`` is a traced
    argument, so retuning tolerance never recompiles.

    ``rank_k > 0`` adds the Peserico–Pretto rank-stability rule: a column
    also stops once the *ordering* of its top-``rank_k`` authority entries
    has been unchanged for ``stable_sweeps`` consecutive sweeps — score
    convergence can lag rank convergence arbitrarily, so on slow-spectral
    graphs this saves most of the sweeps at unchanged top-k. The check
    runs on the in-loop (unnormalized) authority, which orders identically
    to the normalized scores; ties break to the lowest index
    (``lax.top_k`` semantics). ``rank_k``/``stable_sweeps`` are static: at
    ``rank_k=0`` the carry and trace are bit-identical to the
    residual-only loop.

    ``bulk_dtype`` (static dtype string) arms the precision ladder inside
    the SAME dispatch: a low-precision copy of the loop — operating on
    ``lt_blocks_lo``/``l_blocks_lo`` (the caller's cast of the operators)
    with f32 accumulation — runs first until its residual reaches
    ``bulk_tol`` (the bulk dtype's floor), then hands its vectors to the
    full-precision loop. ``max_iter`` bounds the TOTAL sweep count; the
    rank-stability state resets at the phase boundary (low-precision
    orderings certify nothing).

    lt_*: the transpose operator (authority half-step), l_*: the forward
    operator (hub half-step); h0/ca/ch/mask: (n_pad, V). Returns
    (h, a, conv, res) — per-column L1-normalized fixed-point vectors, the
    int32 sweep counts, and the residual certificate: one extra
    full-precision sweep's L1 movement ``‖sweep(h) − h‖₁`` at the
    published h. Matches the host-driven loop bit-for-bit in exact
    arithmetic (identical op order and normalization eps).
    """
    def half(blocks, idx, x, cin, accum):
        return _bsr_scaled_matvec(blocks, idx, x, cin, bs=bs,
                                  interpret=interpret, accum_dtype=accum)

    def make_sweep(tb, fb, cav, chv, mv, accum):
        def sweep(h):
            a = half(tb, lt_idx, h, chv, accum) * mv
            h_new = half(fb, l_idx, a, cav, accum) * mv
            return h_new / (jnp.sum(jnp.abs(h_new), axis=0, keepdims=True)
                            + 1e-30), a
        return sweep

    k_eff = min(int(rank_k), h0.shape[0]) if rank_k else 0
    v = h0.shape[1]

    def loop(sweep_fn, h_init, k_init, stop_tol):
        def body(state):
            if k_eff:
                h, k, conv, top_prev, stab = state
            else:
                h, k, conv = state
            h_new, a = sweep_fn(h)
            delta = jnp.sum(jnp.abs(h_new - h), axis=0)      # (V,)
            stop = delta <= stop_tol
            if k_eff:
                top = jax.lax.top_k(a.T, k_eff)[1]           # (V, k) int32
                same = jnp.all(top == top_prev, axis=1)
                stab = jnp.where(same, stab + 1, 0)
                stop = stop | (stab >= stable_sweeps)
                conv = jnp.where((conv < 0) & stop, k + 1, conv)
                return h_new, k + 1, conv, top, stab
            conv = jnp.where((conv < 0) & stop, k + 1, conv)
            return h_new, k + 1, conv

        def cond(state):
            k, conv = state[1], state[2]
            return jnp.logical_and(k < max_iter, jnp.any(conv < 0))

        init = (h_init, k_init, jnp.full((v,), -1, jnp.int32))
        if k_eff:
            init = init + (jnp.full((v, k_eff), -1, jnp.int32),
                           jnp.zeros((v,), jnp.int32))
        state = jax.lax.while_loop(cond, body, init)
        return state[0], state[1], state[2]

    sweep_hi = make_sweep(lt_blocks, l_blocks, ca, ch, mask, accum_dtype)
    k0 = jnp.array(0, jnp.int32)
    if bulk_dtype is not None:
        sweep_lo = make_sweep(lt_blocks_lo, l_blocks_lo,
                              ca.astype(bulk_dtype), ch.astype(bulk_dtype),
                              mask.astype(bulk_dtype), jnp.float32)
        h_lo, k0, _ = loop(sweep_lo, h0.astype(bulk_dtype), k0, bulk_tol)
        h0 = h_lo.astype(h0.dtype)
    h, k, conv = loop(sweep_hi, h0, k0, tol)
    conv = jnp.where(conv < 0, k, conv)  # hit max_iter (or max_iter == 0)
    # finalize + certificate: one extra full-precision sweep recomputes the
    # authority from the converged h (as the host loop and hits._finalize
    # do) and bounds the published residual
    h2, a = sweep_hi(h)
    res = jnp.sum(jnp.abs(h2 - h), axis=0)
    a = a / (jnp.sum(jnp.abs(a), axis=0, keepdims=True) + 1e-30)
    return h, a, conv, res
