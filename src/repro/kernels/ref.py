"""Pure-jnp oracles for the Pallas kernels (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def bsr_scaled_matvec_ref(blocks, idx, x, cin, n_pad: int):
    """Dense-equivalent y = A @ (x * cin), A assembled from BSR blocks."""
    bs = blocks.shape[1]
    xs = (x * cin).astype(jnp.float32)
    y = jnp.zeros((n_pad, x.shape[1]), jnp.float32)

    def body(k, y):
        r, c = idx[k, 0], idx[k, 1]
        xb = jax.lax.dynamic_slice_in_dim(xs, c * bs, bs, axis=0)
        contrib = blocks[k].astype(jnp.float32) @ xb
        cur = jax.lax.dynamic_slice_in_dim(y, r * bs, bs, axis=0)
        return jax.lax.dynamic_update_slice_in_dim(y, cur + contrib, r * bs, axis=0)

    y = jax.lax.fori_loop(0, blocks.shape[0], body, y)
    return y.astype(x.dtype)

