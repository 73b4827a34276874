"""jit'd wrappers + host-side preprocessing for the Pallas kernels."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from ..graph.structure import BSR, Graph, to_bsr
from .bsr_spmm import bsr_converge_cols, bsr_scaled_matvec, resolve_interpret


# ---------------------------------------------------------------- BSR path
def pad_empty_rows(bsr: BSR) -> BSR:
    """Insert a zero block at (r, 0) for every empty block-row so the kernel's
    revisit/init logic writes every output tile."""
    present = np.zeros(bsr.n_block_rows, bool)
    present[bsr.brow] = True
    missing = np.nonzero(~present)[0].astype(np.int32)
    if missing.size == 0:
        return bsr
    bs = bsr.bs
    blocks = np.concatenate([bsr.blocks,
                             np.zeros((len(missing), bs, bs), np.float32)])
    brow = np.concatenate([bsr.brow, missing])
    bcol = np.concatenate([bsr.bcol, np.zeros(len(missing), np.int32)])
    order = np.argsort(brow, kind="stable")
    counts = np.bincount(brow, minlength=bsr.n_block_rows)
    row_ptr = np.zeros(bsr.n_block_rows + 1, np.int64)
    np.cumsum(counts, out=row_ptr[1:])
    return BSR(bsr.n_nodes, bs, blocks[order], brow[order].astype(np.int32),
               bcol[order].astype(np.int32), row_ptr)


@dataclasses.dataclass(frozen=True)
class DeviceBSR:
    """Device-resident BSR ready for the Pallas kernel."""

    blocks: jnp.ndarray  # (nblocks, bs, bs)
    idx: jnp.ndarray     # (nblocks, 2) int32 (brow, bcol) sorted by brow
    bs: int
    n_nodes: int
    n_pad: int

    @staticmethod
    def build(g: Graph, bs: int = 128, transpose: bool = False,
              dtype=jnp.float32,
              values: np.ndarray | None = None) -> "DeviceBSR":
        """``values`` are per-edge weights in g's edge order (default 1.0);
        ``reverse()`` preserves edge order, so they apply to either side."""
        gg = g.reverse() if transpose else g
        bsr = pad_empty_rows(to_bsr(gg, bs, values=values))
        idx = np.stack([bsr.brow, bsr.bcol], axis=1).astype(np.int32)
        return DeviceBSR(jnp.asarray(bsr.blocks, dtype), jnp.asarray(idx),
                         bs, g.n_nodes, bsr.n_padded)


def bsr_revalue(idx: np.ndarray, bs: int, n_pad: int, src: np.ndarray,
                dst: np.ndarray, vals: np.ndarray,
                dtype=np.float64) -> np.ndarray | None:
    """Re-scatter new edge values into an existing BSR block layout.

    ``idx`` is a DeviceBSR's (nblocks, 2) (brow, bcol) table — sorted
    lexicographically, which ``pad_empty_rows`` guarantees (per-row blocks
    come bcol-sorted out of ``to_bsr``'s unique pass; padding rows get a
    single bcol=0 block; the final sort is brow-stable). ``src``/``dst``/
    ``vals`` are the edges in the layout's own (permuted) node space.

    Returns the new (nblocks, bs, bs) host block array, or None when an
    edge falls in a block absent from the layout — the caller must then
    rebuild the structure rather than patch it. This is the value-only
    half of a weight delta: the blocking permutation, idx table, and
    kernel grid all survive untouched.
    """
    idx = np.asarray(idx)
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    nbr = n_pad // bs
    ikey = idx[:, 0].astype(np.int64) * nbr + idx[:, 1]
    bkey = (src // bs) * nbr + (dst // bs)
    pos = np.searchsorted(ikey, bkey)
    if bkey.size and (np.any(pos >= len(ikey))
                      or np.any(ikey[np.minimum(pos, len(ikey) - 1)] != bkey)):
        return None
    blocks = np.zeros((len(ikey), bs, bs), dtype)
    np.add.at(blocks, (pos, src % bs, dst % bs), np.asarray(vals, dtype))
    return blocks


def bsr_matvec(dbsr: DeviceBSR, x, cin=None, interpret: bool | None = None,
               accum_dtype=jnp.float32):
    """y = A @ (x * cin). x: (N,) | (N, V); cin: None | (N,) shared diagonal
    | (N, V) per-column diagonals; returns the shape matching x."""
    squeeze = x.ndim == 1
    xv = x[:, None] if squeeze else x
    pad = dbsr.n_pad - xv.shape[0]
    xv = jnp.pad(xv, ((0, pad), (0, 0)))
    if cin is None:
        cv = jnp.ones((dbsr.n_pad, 1), xv.dtype)
    else:
        cv = cin[:, None] if cin.ndim == 1 else cin
        cv = jnp.pad(cv.astype(xv.dtype), ((0, pad), (0, 0)))
    y = bsr_scaled_matvec(dbsr.blocks, dbsr.idx, xv, cv, bs=dbsr.bs,
                          interpret=interpret, accum_dtype=accum_dtype)
    y = y[: dbsr.n_nodes]
    return y[:, 0] if squeeze else y


def bsr_converge(lt: DeviceBSR, lfwd: DeviceBSR, h0, ca, ch, mask, tol,
                 max_iter: int, interpret: bool | None = None,
                 accum_dtype=jnp.float32, perm=None, inv=None,
                 rank_k: int = 0, stable_sweeps: int = 2,
                 lt_lo: DeviceBSR | None = None,
                 lfwd_lo: DeviceBSR | None = None,
                 bulk_tol: float = 0.0, bulk_dtype=None):
    """Fused on-device convergence loop over a DeviceBSR operator pair.

    a = Lᵀ(h ⊙ ch)·mask;  h' = L(a ⊙ ca)·mask;  h' ← h'/‖h'‖₁, iterated by
    ``bsr_converge_cols``'s ``lax.while_loop`` until every column's L1
    residual hits ``tol`` (or ``max_iter``) — one device dispatch per
    batch, no per-iteration host sync. h0/ca/ch/mask: (n, V) with
    n <= lt.n_pad (rows pad with zeros and slice back off). Returns
    (h, a, conv, res) shaped like the inputs — ``res`` is the per-column
    residual certificate from one extra full-precision sweep.

    ``bulk_dtype`` (a dtype string) arms the kernel's precision ladder;
    it requires ``lt_lo``/``lfwd_lo``, the operator pair cast to that
    dtype, and ``bulk_tol``, the bulk phase's stop tolerance.

    ``perm``/``inv``: optional (n,) node permutation (new -> old) and its
    inverse when the operators were built in a reordered space (the BSR
    blocking permutation). Inputs are gathered by ``perm`` at the loop
    entry and results scattered back by ``inv`` at the exit via
    ``jnp.take`` — the whole per-batch vector permutation stays on
    device, with outputs in the caller's original node order.

    ``rank_k``/``stable_sweeps`` pass through to the kernel loop's
    rank-stability early exit. Note the stability check runs in the
    *operator's* node order (i.e. permuted space when ``perm`` is given):
    whether an ordering repeats across sweeps is permutation-invariant,
    so stopping sweeps agree with the dense backend up to tie-breaks
    among exactly-equal scores.
    """
    assert lt.bs == lfwd.bs and lt.n_pad == lfwd.n_pad, "mismatched operators"
    if bulk_dtype is not None and (lt_lo is None or lfwd_lo is None):
        raise ValueError("bulk_dtype set but lt_lo/lfwd_lo operators missing")
    n = h0.shape[0]
    pad = lt.n_pad - n
    args = (h0, ca, ch, mask)
    if perm is not None:
        perm = jnp.asarray(perm)
        # a mis-sized permutation would silently clamp-gather wrong rows
        assert perm.shape[0] == n, (perm.shape, n)
        args = tuple(jnp.take(x, perm, axis=0) for x in args)
    if pad:
        args = tuple(jnp.pad(x, ((0, pad), (0, 0))) for x in args)
    h, a, conv, res = bsr_converge_cols(
        lt.blocks, lt.idx, lfwd.blocks, lfwd.idx, *args, tol,
        bs=lt.bs, interpret=resolve_interpret(interpret),
        accum_dtype=accum_dtype, max_iter=max_iter,
        rank_k=int(rank_k), stable_sweeps=int(stable_sweeps),
        lt_blocks_lo=None if lt_lo is None else lt_lo.blocks,
        l_blocks_lo=None if lfwd_lo is None else lfwd_lo.blocks,
        bulk_tol=bulk_tol, bulk_dtype=bulk_dtype)
    h, a = h[:n], a[:n]
    if inv is not None:
        inv = jnp.asarray(inv)
        assert inv.shape[0] == n, (inv.shape, n)
        h, a = jnp.take(h, inv, axis=0), jnp.take(a, inv, axis=0)
    return h, a, conv, res


def classify_exit(conv, res, tol: float, max_iter: int, rank_k: int = 0,
                  stable_sweeps: int = 2):
    """Per-column convergence exit reasons, classified host-side from what
    every backend's loop already returns: ``conv`` (sweeps used) and
    ``res`` (the one-extra-sweep residual certificate at the published
    vectors).

    The fused loops deliberately do not carry an explicit reason through
    their ``lax.while_loop`` state (a wider carry would perturb the
    bit-identity pins the rank_k=0 path holds), so the reason is inferred:

    * ``max_iter``    — the column spent the full budget: neither stopping
      rule fired.
    * ``rank_stable`` — rank-stability stopping was armed and the column
      stopped with its certified residual still above ``tol``: only the
      top-k-ordering rule can have released it (Peserico & Pretto's
      rank-before-score convergence, visible in live telemetry).
    * ``residual``    — the L1 residual reached ``tol`` (with rank_k on,
      a column whose scores converged before — or in the same sweep as —
      its ordering stabilized also lands here: the certificate can't tell
      those apart, and for operations they're the same healthy exit).

    Returns a list of reason strings, one per column of ``conv``.
    """
    conv = np.asarray(conv)
    res = np.asarray(res)
    out = []
    for c, r in zip(conv.ravel(), res.ravel()):
        if int(c) >= int(max_iter):
            out.append("max_iter")
        elif rank_k > 0 and float(r) > float(tol):
            out.append("rank_stable")
        else:
            out.append("residual")
    return out


def hits_sweep_bsr(g: Graph, ca=None, ch=None, bs: int = 128,
                   interpret: bool | None = None, dtype=jnp.float32):
    """Accelerated-HITS sweep on the BSR kernel path.

    a = Lᵀ(h ⊙ ch);  h' = L(a ⊙ ca);  h' ← h'/‖h'‖₁. Returns sweep(h)->(h',a)
    plus the two DeviceBSR structures (LT for the authority step, L for the
    hub step).
    """
    lt = DeviceBSR.build(g, bs, transpose=True, dtype=dtype)
    l = DeviceBSR.build(g, bs, transpose=False, dtype=dtype)
    ca_j = None if ca is None else jnp.asarray(ca, dtype)
    ch_j = None if ch is None else jnp.asarray(ch, dtype)

    def sweep(h):
        a = bsr_matvec(lt, h, ch_j, interpret)
        h_new = bsr_matvec(l, a, ca_j, interpret)
        h_new = h_new / (jnp.sum(jnp.abs(h_new), axis=0, keepdims=h.ndim > 1) + 1e-30)
        return h_new, a

    return sweep, lt, l

