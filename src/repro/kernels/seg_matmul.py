"""Tiled segment-sum as one-hot MXU matmul — Pallas TPU kernel.

The scatter half of message passing (GIN aggregation, EmbeddingBag reduce,
HITS edge scatter): given messages already gathered per edge and edges
sorted by destination, accumulate each destination row. TPUs have no fast
random scatter; the TPU-native trick is to turn a (tile_e,)-edge scatter
into a dense (bs × tile_e) × (tile_e × F) matmul with a one-hot selector
built in-registers — MXU work instead of serialized memory traffic.

Preprocessing (ops.build_tiled_segments) pads each destination block's edge
run to a whole number of tiles, so a grid step touches exactly one output
block; steps sharing a block revisit it in VMEM (single HBM write per
block, same pattern as bsr_spmm).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _seg_kernel(blkid_ref, msgs_ref, off_ref, valid_ref, y_ref, *, bs,
                accum_dtype):
    t = pl.program_id(0)
    blk_t = blkid_ref[t]
    blk_prev = blkid_ref[jnp.maximum(t - 1, 0)]
    is_first = jnp.logical_or(t == 0, blk_t != blk_prev)

    @pl.when(is_first)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    msgs = msgs_ref[...].astype(accum_dtype)            # (tile_e, F)
    off = off_ref[...]                                  # (tile_e, 1) int32
    valid = valid_ref[...].astype(accum_dtype)          # (tile_e, 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bs, off.shape[0]), 0)
    onehot = (rows == off[:, 0][None, :]).astype(accum_dtype)  # (bs, tile_e)
    onehot = onehot * valid[:, 0][None, :]
    # f32 messages contract in f32 (Mosaic's default is one bf16 pass)
    precision = (None if msgs_ref.dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    y_ref[...] += jnp.dot(onehot, msgs, preferred_element_type=accum_dtype,
                          precision=precision).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_blocks", "bs", "interpret",
                                              "accum_dtype"))
def seg_matmul(blkid, msgs, off, valid, n_blocks: int, *, bs: int = 128,
               interpret: bool = True, accum_dtype=jnp.float32):
    """Segment-sum messages into (n_blocks*bs, F).

    blkid: (n_tiles,) int32 destination block per edge tile (sorted).
    msgs:  (n_tiles*tile_e, F) gathered messages (padded with zeros).
    off:   (n_tiles*tile_e, 1) int32 destination offset within block.
    valid: (n_tiles*tile_e, 1) 0/1 mask for padding edges.
    """
    n_tiles = blkid.shape[0]
    tile_e = msgs.shape[0] // n_tiles
    f = msgs.shape[1]

    # i32 block indices, made inside the maps (see bsr_spmm: a Python 0
    # is i64 under jax_enable_x64, which Mosaic refuses)
    def tile_t(t, blkid_ref):
        return t, jnp.int32(0)

    def out_block(t, blkid_ref):
        return blkid_ref[t], jnp.int32(0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile_e, f), tile_t),
            pl.BlockSpec((tile_e, 1), tile_t),
            pl.BlockSpec((tile_e, 1), tile_t),
        ],
        out_specs=pl.BlockSpec((bs, f), out_block),
    )
    return pl.pallas_call(
        functools.partial(_seg_kernel, bs=bs, accum_dtype=accum_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks * bs, f), msgs.dtype),
        interpret=interpret,
    )(blkid, msgs, off, valid)
