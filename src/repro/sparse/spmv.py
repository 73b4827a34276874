"""Single-device sparse mat-vec / mat-multivec via gather + segment_sum.

JAX has no CSR/CSC (BCOO only), so the portable sparse primitive is an
edge-list scatter-add: ``segment_sum(x[gather] * w, scatter)``. All ranking
algorithms are built on these two ops. The
Pallas BSR kernel (repro.kernels.bsr_spmm) is the TPU hot path for the same
contraction; these functions are its semantic reference.

Vectors may be (N,) or (N, V) — multi-vector iteration batches V ranking
vectors through one traversal (MXU-friendly).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _bcast_w(w, x_g):
    return w[:, None] if (w is not None and x_g.ndim == 2) else w


def spmv_dst(x, src, dst, n, w=None):
    """out[j] = sum over edges (i->j) of x[i] * w_e  — i.e. xᵀ·L gathered at dst.

    This is the authority update: a = spmv_dst(h·ch, ...).
    """
    x_g = jnp.take(x, src, axis=0)
    if w is not None:
        x_g = x_g * _bcast_w(w, x_g)
    return jax.ops.segment_sum(x_g, dst, num_segments=n)


def spmv_src(x, src, dst, n, w=None):
    """out[i] = sum over edges (i->j) of x[j] * w_e  — i.e. xᵀ·Lᵀ gathered at src.

    This is the hub update: h = spmv_src(a·ca, ...).
    """
    x_g = jnp.take(x, dst, axis=0)
    if w is not None:
        x_g = x_g * _bcast_w(w, x_g)
    return jax.ops.segment_sum(x_g, src, num_segments=n)


def normalize_l1(x, axis=0, eps=1e-30):
    return x / (jnp.sum(jnp.abs(x), axis=axis, keepdims=x.ndim > 1) + eps)


def residual_l1(x, y, axis=0):
    d = jnp.sum(jnp.abs(x - y), axis=axis)
    return jnp.max(d) if d.ndim else d
