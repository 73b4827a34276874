"""Pluggable sweep backends for the query-ranking service.

``RankService`` assembles one padded union-subgraph batch per traversal —
(n_pad, V) start vectors, per-column induced Ca/Ch weights and base-set
masks, and a sentinel-padded edge list — and hands it to a backend that
runs the masked multi-column accelerated-HITS convergence loop:

* ``dense``   — single-device ``core.hits.column_sweep`` under a jitted
                ``lax.while_loop``, each pass a sorted segment sum
                (``sparse.segsum``) over edges the plan orders by
                destination and by source: no scatter.
* ``sharded`` — the same column sweep lowered onto a device mesh through
                ``sparse.dist.make_dist_hits_sweep_cols``; edge shards
                follow the dist ladder (``replicated``: 2 psums/sweep,
                ``dual_blocked``: 2 all-gathers/sweep).
* ``bsr``     — the Pallas block-sparse kernel (``kernels.bsr_spmm``) with
                per-column fused diagonals, after ``core.reordering``
                blocking (non-dangling-first node order so nonzeros cluster
                into dense blocks) — the dense-block accelerator regime.
                The convergence loop fuses on-device by default
                (``kernels.bsr_converge_cols``: ``lax.while_loop`` around
                the Pallas sweep, one dispatch per batch); ``fused=False``
                keeps the host-driven loop as the parity reference.

Each backend splits its work along the plan/sweep seam (``serve.plans``):
``plan(batch)`` builds the graph-structure-only artifact — device edge
list sorted by destination and by source (dense), pow2-bucketed device
edge shards + the shared mesh (sharded), blocking permutation + both BSR
structures (bsr) — and
``sweep(plan, batch)`` runs the convergence loop against it.
``converge(batch)`` is the uncached composition; ``RankService`` LRU-caches
plans per union-subgraph hash so repeat traffic skips all host-side layout
rebuilding.

All backends compute the same fixed point (the parity suite holds them to
<=1e-10 L1 of the dense oracle), so everything above the interface —
batching, caching, warm starts, and every later scaling PR — is
backend-agnostic.

Every backend's loop returns ``(h, a, conv, res)``: per-column sweep
counts and a one-extra-sweep residual certificate. The serving layer
turns those into convergence telemetry — ``service.sweep.iters`` and the
per-column exit reason (``kernels.ops.classify_exit``: residual vs
rank-stability vs budget exhaustion) — without widening any kernel's
while-loop carry. See ``docs/ARCHITECTURE.md`` for where backends sit in
the stack and ``docs/OPERATIONS.md`` for the emitted metrics.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.hits import column_sweep
from ..core.reordering import blocking_permutation
from ..graph.structure import Graph
from ..kernels.bsr_spmm import resolve_interpret
from ..kernels.ops import DeviceBSR, bsr_converge, bsr_matvec, bsr_revalue
from ..sparse.dist import (build_edge_shards_cols, collective_bytes,
                           collective_bytes_per_sweep_cols,
                           device_put_edge_args_cols,
                           make_dist_hits_sweep_cols,
                           wire_bytes_from_collectives)
from ..sparse.segsum import (SortedEdges, reweight, segment_sum_cols,
                             sort_edges)
from ..sparse.spmv import normalize_l1
from .plans import (BsrPlan, DensePlan, ShardedPlan, SweepPlan,
                    structure_key)
from .telemetry import span

BACKENDS = ("dense", "sharded", "bsr")

# auto heuristic: sharding pays once the union subgraph's per-sweep edge
# work dwarfs the collective latency; BSR pays in the dense-block regime
# when the Pallas path actually compiles (TPU)
_SHARD_MIN_EDGES = 4096
_BSR_MIN_EDGES_PER_NODE = 8.0

# --------------------------------------------------------- precision ladder
#
# The ladder runs the bulk of convergence sweeps at a cheap dtype
# (bf16/fp32), then an f64 polish phase iterates to the configured tol and
# the published result carries an explicit residual certificate. These
# helpers are THE switch-over criterion — all three backends (and
# RankService's own tol clamp) share them, so the ladder stops its bulk
# phase at exactly the residual the bulk dtype can still resolve.

# accepted spellings for RankServiceConfig.sweep_dtype
_SWEEP_DTYPES = {
    "bf16": "bfloat16", "bfloat16": "bfloat16",
    "fp32": "float32", "f32": "float32", "float32": "float32",
    "fp64": "float64", "f64": "float64", "float64": "float64",
}


def resolve_sweep_dtype(name):
    """Canonical numpy dtype for a ``sweep_dtype`` spelling; ''/None
    disables the ladder (returns None). Raises ValueError on junk."""
    if name is None or name == "":
        return None
    if not isinstance(name, str):
        return np.dtype(jnp.zeros((), name).dtype)  # already dtype-like
    canon = _SWEEP_DTYPES.get(name.lower())
    if canon is None:
        raise ValueError(f"unknown sweep_dtype {name!r} "
                         f"(want one of {sorted(set(_SWEEP_DTYPES))})")
    return np.dtype(canon)


def dtype_floor(dtype) -> float:
    """The smallest L1 residual iteration at ``dtype`` can reliably
    resolve: 1e3 * eps (the same clamp ``RankService.__init__`` applies to
    ``tol``). Below this a low-precision sweep's residual has stalled at
    its dtype floor — further sweeps are rounding noise, not progress."""
    return 1e3 * float(jnp.finfo(jnp.zeros((), dtype).dtype).eps)


def bulk_stop_tol(bulk_dtype, tol: float) -> float:
    """The ladder's switch-over tolerance: the bulk phase stops once its
    residual reaches max(tol, the bulk dtype's floor), then hands its
    vectors to the full-precision polish loop."""
    return max(float(tol), dtype_floor(bulk_dtype))


@dataclasses.dataclass(frozen=True)
class SweepBatch:
    """One padded serving batch (host arrays; see ServePipeline.assemble).

    h0/ca/ch/mask: (n_pad, V); src/dst/w: (e_pad,) with sentinel edges
    pointing at the dead pad row n_pad-1 carrying w=0.

    ``rank_k``/``stable_sweeps`` are the rank-stability stopping params
    every backend honors identically: with ``rank_k > 0`` a column also
    stops once its top-``rank_k`` authority ordering has been unchanged
    for ``stable_sweeps`` consecutive sweeps (Peserico–Pretto early
    exit); ``rank_k=0`` is the exact-residual-only legacy rule.

    ``bulk_dtype`` arms the precision ladder: a non-None dtype runs the
    bulk of sweeps at that precision until the residual reaches
    ``bulk_stop_tol(bulk_dtype, tol)``, then the full-precision polish
    loop iterates to ``tol``. None is the single-phase legacy loop
    (bit-identical trace).

    ``lump_key`` marks a batch whose arrays are the lump-reduced form of a
    full assembled batch (``serve.plans.lump_batch``): the reduction map's
    content hash. It joins the service plan-cache key so lumped and
    unlumped plans never alias; '' is an ordinary full-space batch.
    """

    h0: np.ndarray
    src: np.ndarray
    dst: np.ndarray
    w: np.ndarray
    ca: np.ndarray
    ch: np.ndarray
    mask: np.ndarray
    tol: float
    max_iter: int
    dtype: object
    rank_k: int = 0
    stable_sweeps: int = 2
    bulk_dtype: object = None
    lump_key: str = ""

    def structure_key(self) -> str:
        """Hash of the structure-only fields a plan may depend on."""
        return structure_key(self.src, self.dst, self.w, self.h0.shape[0],
                             self.dtype)

    def ladder_key(self) -> str:
        """The batch's precision-ladder marker ('' = single-phase) — part
        of the service plan-cache key, so plans built for different
        ladders (e.g. the bsr backend's low-precision operator copies)
        never alias."""
        return "" if self.bulk_dtype is None else str(np.dtype(self.bulk_dtype))

    def bulk_tol(self) -> float:
        """The bulk phase's stop tolerance (0.0 when the ladder is off)."""
        return (0.0 if self.bulk_dtype is None
                else bulk_stop_tol(self.bulk_dtype, self.tol))


class SweepBackend:
    """Interface: plan the structure, then converge batches against it.

    ``plan(batch)`` consumes only the batch's structural fields (src/dst/w,
    n_pad, dtype — plus the ladder's ``bulk_dtype``, which keys the plan
    cache) and returns the backend's ``SweepPlan``;
    ``sweep(plan, batch)`` runs the convergence loop and returns
    (h, a, conv, res) numpy arrays — ``h``/``a`` are (n_pad, V) per-column
    L1-normalized hub/authority vectors at the fixed point, ``conv[j]`` the
    sweep at which column j first hit tol (== max_iter when it never did),
    and ``res[j]`` the residual certificate: the L1 distance one more
    full-precision sweep moves the published h — ``‖sweep(h) − h‖₁`` —
    so a ladder (or legacy) result's convergence claim is checkable
    without trusting the loop that produced it. ``converge(batch)`` is the
    uncached composition. ``plan_params()`` feeds the plan-cache key:
    every backend knob that changes the plan's layout must appear in it.
    """

    name: str = "?"

    def plan_params(self) -> tuple:
        return ()

    def plan(self, batch: SweepBatch, key: str = "") -> SweepPlan:
        raise NotImplementedError

    def sweep(self, plan: SweepPlan, batch: SweepBatch
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        raise NotImplementedError

    def converge(self, batch: SweepBatch
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        return self.sweep(self.plan(batch), batch)

    def plan_arrays(self, plan: SweepPlan) -> Tuple[Dict, dict]:
        """The plan's persistable form: ({name: host array}, json-meta).

        ``serve.spill.PlanSpill`` checkpoints these next to the vector
        spill; ``plan_restore`` rehydrates them into a device-resident
        plan WITHOUT redoing the layout work (partitioning, blocking,
        permutation) — the whole point of persisting plans.
        """
        raise NotImplementedError

    def plan_restore(self, key: str, arrays: Dict, meta: dict) -> SweepPlan:
        """Inverse of ``plan_arrays`` (raise/return garbage-intolerant:
        callers treat any failure as a rebuild)."""
        raise NotImplementedError

    def patch(self, plan: SweepPlan, batch: SweepBatch,
              key: str = "") -> Optional[SweepPlan]:
        """Value-only update: a plan for ``batch`` built from ``plan``.

        ``plan`` and ``batch`` share a ``plans.topology_key`` — same padded
        endpoints, different edge weights (an edge-weight delta). Backends
        that can reuse the old plan's layout (device edge lists, blocking
        permutation, block index tables) return the patched plan, keyed by
        ``key`` (the batch's new structure_key); backends whose layout
        bakes the weights in — or any case where the old layout can't hold
        the new values — return None and the caller does a full replan.
        """
        return None

    def _check(self, plan: SweepPlan, batch: SweepBatch):
        # cheap structural guard (the full content hash already gated the
        # cache lookup; re-hashing here would double the host cost)
        if plan.backend != self.name or plan.n_pad != batch.h0.shape[0]:
            raise ValueError(
                f"plan {plan.backend!r}/n_pad={plan.n_pad} does not fit "
                f"batch {self.name!r}/n_pad={batch.h0.shape[0]}")


# ------------------------------------------------------------------- dense


@partial(jax.jit, static_argnames=("max_iter", "rank_k", "stable_sweeps",
                                   "bulk_dtype"))
def _converge_batch(h0, by_dst, by_src, ca, ch, mask, tol, max_iter,
                    rank_k=0, stable_sweeps=2, bulk_dtype=None,
                    bulk_tol=0.0):
    """On-device convergence loop for V masked columns, over the union's
    edges sorted by destination and by source (``DensePlan``).

    Per-column L1 residuals; ``conv[j]`` records the sweep at which column
    j first hit tol (-1 while running). All columns keep sweeping until the
    last converges — converged columns sit at their fixed point.
    ``rank_k > 0`` adds the rank-stability stop (ordering of the top-k
    in-loop authority entries unchanged ``stable_sweeps`` sweeps running);
    it is static, so ``rank_k=0`` traces the legacy residual-only loop.
    ``bulk_dtype`` (a static dtype string) arms the precision ladder: a
    low-precision copy of the same loop runs first to ``bulk_tol``, hands
    its vectors to the full-precision loop, and ``max_iter`` bounds the
    TOTAL sweep count across both phases. Rank-stability state resets at
    the phase boundary (low-precision orderings don't certify anything).
    Returns (h, a, conv, res) — ``res`` is the per-column certificate
    ``‖sweep(h) − h‖₁`` from one extra full-precision sweep.
    """
    n, v = h0.shape

    def sweep_at(dtype):
        def pass_(edges):
            edges = edges._replace(w=edges.w.astype(dtype))
            return lambda x: segment_sum_cols(x, edges)
        return column_sweep(pass_(by_dst), pass_(by_src), ca.astype(dtype),
                            ch.astype(dtype), mask.astype(dtype))

    sweep = sweep_at(h0.dtype)
    k_eff = min(int(rank_k), n) if rank_k else 0

    def loop(sweep_fn, h_init, k_init, stop_tol):
        def body(state):
            if k_eff:
                h, _a, k, conv, top_prev, stab = state
            else:
                h, _a, k, conv = state
            h_new, a = sweep_fn(h)
            delta = jnp.sum(jnp.abs(h_new - h), axis=0)      # (V,)
            stop = delta <= stop_tol
            if k_eff:
                top = jax.lax.top_k(a.T, k_eff)[1]           # (V, k) int32
                same = jnp.all(top == top_prev, axis=1)
                stab = jnp.where(same, stab + 1, 0)
                stop = stop | (stab >= stable_sweeps)
                conv = jnp.where((conv < 0) & stop, k + 1, conv)
                return h_new, a, k + 1, conv, top, stab
            conv = jnp.where((conv < 0) & stop, k + 1, conv)
            return h_new, a, k + 1, conv

        def cond(state):
            k, conv = state[2], state[3]
            return jnp.logical_and(k < max_iter, jnp.any(conv < 0))

        init = (h_init, jnp.zeros_like(h_init), k_init,
                jnp.full((v,), -1, jnp.int32))
        if k_eff:
            init = init + (jnp.full((v, k_eff), -1, jnp.int32),
                           jnp.zeros((v,), jnp.int32))
        state = jax.lax.while_loop(cond, body, init)
        return state[0], state[2], state[3]

    k0 = jnp.array(0, jnp.int32)
    if bulk_dtype is not None:
        # bulk phase: same loop at the cheap dtype, stopping at the dtype's
        # residual floor; its sweep count carries into the polish phase so
        # max_iter bounds total work
        h_lo, k0, _ = loop(sweep_at(bulk_dtype), h0.astype(bulk_dtype), k0,
                           bulk_tol)
        h0 = h_lo.astype(h0.dtype)
    h, k, conv = loop(sweep, h0, k0, tol)
    conv = jnp.where(conv < 0, k, conv)  # hit max_iter
    # finalize + certificate: one extra full-precision sweep from the
    # published h yields both the recomputed authority (same as
    # hits._finalize) and the residual bound ‖sweep(h) − h‖₁
    with jax.named_scope("hits.certificate"):
        h2, a = sweep(h)
        res = jnp.sum(jnp.abs(h2 - h), axis=0)
    return h, normalize_l1(a, axis=0), conv, res


class DenseSweepBackend(SweepBackend):
    """Single-device sorted segment sums (the f64 serving path)."""

    name = "dense"

    def plan(self, b: SweepBatch, key: str = "") -> DensePlan:
        # the dense layout is the edge list by destination and by source,
        # on the device: cached plans skip the sorts and the transfer
        n_pad = b.h0.shape[0]
        w = np.asarray(b.w, np.dtype(b.dtype))
        with span("backend.order"):
            by_dst = sort_edges(b.dst, b.src, w, n_pad)
            by_src = sort_edges(b.src, b.dst, w, n_pad)
        return DensePlan(key=key or b.structure_key(), backend=self.name,
                         n_pad=n_pad, by_dst=by_dst, by_src=by_src)

    def plan_arrays(self, plan: DensePlan):
        arrays = {f"{way}_{field}": np.asarray(x)
                  for way, edges in (("by_dst", plan.by_dst),
                                     ("by_src", plan.by_src))
                  for field, x in edges._asdict().items()}
        return arrays, {"n_pad": int(plan.n_pad)}

    def plan_restore(self, key: str, arrays, meta) -> DensePlan:
        def edges(way):
            return SortedEdges(*(jnp.asarray(arrays[f"{way}_{field}"])
                                 for field in SortedEdges._fields))
        return DensePlan(key=key, backend=self.name,
                         n_pad=int(meta["n_pad"]), by_dst=edges("by_dst"),
                         by_src=edges("by_src"))

    def patch(self, plan: DensePlan, b: SweepBatch,
              key: str = "") -> DensePlan:
        # the sorted endpoints are already on device; only the weight
        # array ships, put into each order by its sort's permutation
        self._check(plan, b)
        w = jnp.asarray(b.w, b.dtype)
        return DensePlan(key=key or b.structure_key(), backend=self.name,
                         n_pad=plan.n_pad, by_dst=reweight(plan.by_dst, w),
                         by_src=reweight(plan.by_src, w))

    def sweep(self, plan: DensePlan, b: SweepBatch):
        self._check(plan, b)
        with span("backend.upload"):
            h0, ca, ch, mask = (jnp.asarray(x, b.dtype)
                                for x in (b.h0, b.ca, b.ch, b.mask))
        with span("backend.converge"):
            # the readback below waits for these anyway: no extra sync
            out = jax.block_until_ready(_converge_batch(
                h0, plan.by_dst, plan.by_src, ca, ch, mask, b.tol,
                b.max_iter, rank_k=int(b.rank_k),
                stable_sweeps=int(b.stable_sweeps),
                bulk_dtype=b.ladder_key() or None, bulk_tol=b.bulk_tol()))
        with span("backend.readback"):
            return tuple(np.asarray(x) for x in out)


# ----------------------------------------------------------------- sharded

# jitted converge per (mesh, mode, shape bucket) — shared across services
_SHARDED_JIT: Dict[tuple, object] = {}

# process-wide mesh per (device subset, axes): meshes are pure structure,
# so every backend instance (and every plan) over the same device subset
# shares ONE object — repeat batches and fresh services alike never build
# a mesh again, and mesh-keyed jit caches keep hitting
_MESH_CACHE: Dict[tuple, object] = {}


def shared_mesh(devices, axes):
    key = (tuple(d.id for d in devices), tuple(axes))
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        mesh = jax.sharding.Mesh(np.asarray(devices), tuple(axes))
        _MESH_CACHE[key] = mesh
    return mesh


def _sharded_converge(mesh, mode, n_pad, per, v, max_iter, dtype, axes,
                      rank_k=0, stable_sweeps=2, bulk_dtype=None):
    k_eff = min(int(rank_k), n_pad) if rank_k else 0
    key = (mesh, mode, n_pad, per, v, max_iter, np.dtype(dtype).str,
           k_eff, int(stable_sweeps), bulk_dtype or "")
    fn = _SHARDED_JIT.get(key)
    if fn is not None:
        return fn
    smapped = make_dist_hits_sweep_cols(mesh, mode, n_pad, axes=axes)

    def converge(h0, ca, ch, m, eargs, tol, bulk_tol):
        lead = tuple(range(h0.ndim - 1))  # (0,) full | (0, 1) blocked

        def loop(args, h_init, k_init, stop_tol):
            cav, chv, mv, ev = args

            def body(state):
                if k_eff:
                    h, _a, k, conv, top_prev, stab = state
                else:
                    h, _a, k, conv = state
                h_new, a = smapped(h, cav, chv, mv, *ev)
                delta = jnp.sum(jnp.abs(h_new - h), axis=lead)
                stop = delta <= stop_tol
                if k_eff:
                    # blocked layouts flatten back to node-major rows; pad
                    # rows are zero and tie-break below every real score
                    top = jax.lax.top_k(a.reshape(-1, v).T, k_eff)[1]
                    same = jnp.all(top == top_prev, axis=1)
                    stab = jnp.where(same, stab + 1, 0)
                    stop = stop | (stab >= stable_sweeps)
                    conv = jnp.where((conv < 0) & stop, k + 1, conv)
                    return h_new, a, k + 1, conv, top, stab
                conv = jnp.where((conv < 0) & stop, k + 1, conv)
                return h_new, a, k + 1, conv

            def cond(state):
                k, conv = state[2], state[3]
                return jnp.logical_and(k < max_iter, jnp.any(conv < 0))

            init = (h_init, jnp.zeros_like(h_init), k_init,
                    jnp.full((v,), -1, jnp.int32))
            if k_eff:
                init = init + (jnp.full((v, k_eff), -1, jnp.int32),
                               jnp.zeros((v,), jnp.int32))
            state = jax.lax.while_loop(cond, body, init)
            return state[0], state[2], state[3]

        k0 = jnp.array(0, jnp.int32)
        if bulk_dtype is not None:
            # bulk phase at the cheap dtype; the dist sweep is
            # dtype-polymorphic so the same shard_map closure traces at
            # both precisions inside this one jit
            cast = (lambda x: x.astype(bulk_dtype)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x)
            eargs_lo = tuple(cast(x) for x in eargs)
            args_lo = (ca.astype(bulk_dtype), ch.astype(bulk_dtype),
                       m.astype(bulk_dtype), eargs_lo)
            h_lo, k0, _ = loop(args_lo, h0.astype(bulk_dtype), k0, bulk_tol)
            h0 = h_lo.astype(h0.dtype)
        h, k, conv = loop((ca, ch, m, eargs), h0, k0, tol)
        conv = jnp.where(conv < 0, k, conv)
        # finalize + certificate: one more full-precision sweep from the
        # published h gives both the recomputed authority and the residual
        # bound ‖sweep(h) − h‖₁
        h2, a = smapped(h, ca, ch, m, *eargs)
        res = jnp.sum(jnp.abs(h2 - h), axis=lead)
        a = a / (jnp.sum(jnp.abs(a), axis=lead, keepdims=True) + 1e-30)
        return h, a, conv, res

    fn = jax.jit(converge)
    _SHARDED_JIT[key] = fn
    return fn


class ShardedSweepBackend(SweepBackend):
    """Mesh-sharded column sweep over the dist.py edge-sharding ladder."""

    name = "sharded"

    def __init__(self, mode: str = "dual_blocked",
                 n_devices: Optional[int] = None, axis: str = "data"):
        if mode not in ("replicated", "dual_blocked"):
            raise ValueError(f"unknown shard mode {mode!r}")
        devices = jax.devices()
        s = len(devices) if n_devices is None else int(n_devices)
        if not 1 <= s <= len(devices):
            raise ValueError(f"n_devices={s} outside [1, {len(devices)}]")
        self.mode = mode
        self.n_shards = s
        self.axes = (axis,)
        self.mesh = shared_mesh(devices[:s], self.axes)
        # edge planes split along their leading shard axis (shard s lives
        # on device s); per-column vectors are replicated on every device
        self._edges = NamedSharding(self.mesh, P(axis, None))
        self._blocked = NamedSharding(self.mesh, P(axis, None, None))
        self._replicated = NamedSharding(self.mesh, P())

    def _put(self, x, sharding, dtype=None):
        return jax.device_put(np.asarray(x, dtype), sharding)

    def collective_bytes_per_sweep(self, n_pad: int, v: int,
                                   itemsize: int = 8) -> int:
        """Analytic per-device wire bytes per sweep (the dist ladder)."""
        return collective_bytes_per_sweep_cols(self.mode, n_pad, v,
                                               self.n_shards, itemsize)

    def plan_params(self) -> tuple:
        return (self.mode, self.n_shards, self.axes)

    def plan(self, b: SweepBatch, key: str = "") -> ShardedPlan:
        """Host-side edge partition + device transfer + the shared mesh —
        everything per-batch work used to rebuild that only depends on the
        union subgraph's structure."""
        n_pad = b.h0.shape[0]
        shards = build_edge_shards_cols(b.src, b.dst, b.w, n_pad,
                                        self.n_shards, self.mode)
        return ShardedPlan(key=key or b.structure_key(), backend=self.name,
                           n_pad=n_pad, mesh=self.mesh, mode=self.mode,
                           n_shards=self.n_shards, per=shards["per"],
                           nb=int(shards.get("nb", 0)),
                           eargs=device_put_edge_args_cols(
                               shards, b.dtype, self._edges))

    def plan_arrays(self, plan: ShardedPlan):
        # the eargs tuple IS the layout (calling-convention order owned by
        # device_put_edge_args_cols); the mesh is process state, rebuilt
        # from the backend's own shared mesh at restore
        arrays = {f"earg{i}": np.asarray(x) for i, x in enumerate(plan.eargs)}
        return arrays, {"n_pad": int(plan.n_pad), "mode": plan.mode,
                        "n_shards": int(plan.n_shards),
                        "per": int(plan.per), "nb": int(plan.nb),
                        "n_eargs": len(plan.eargs)}

    def plan_restore(self, key: str, arrays, meta) -> ShardedPlan:
        if meta["mode"] != self.mode or int(meta["n_shards"]) != self.n_shards:
            raise ValueError("spilled plan laid out for a different "
                             f"shard config: {meta}")
        eargs = tuple(self._put(arrays[f"earg{i}"], self._edges)
                      for i in range(int(meta["n_eargs"])))
        return ShardedPlan(key=key, backend=self.name,
                           n_pad=int(meta["n_pad"]), mesh=self.mesh,
                           mode=self.mode, n_shards=self.n_shards,
                           per=int(meta["per"]), nb=int(meta["nb"]),
                           eargs=eargs)

    def patch(self, plan: ShardedPlan, b: SweepBatch,
              key: str = "") -> Optional[ShardedPlan]:
        """Weight-only update keeping the device shard layout.

        The pow2 bucketing (blocked order, per-shard counts, ``per``,
        ``nb``) is a deterministic function of the kept edge endpoints
        alone, and a weight-only delta preserves the w != 0 keep mask
        (reweight-to-0 is classified structural), so a same-topology
        successor batch repacks into byte-identical endpoint planes — only
        the weight planes change. Repack the weights host-side (the
        ``bsr_revalue`` analogue for shard buckets) and ship just those;
        the device endpoint arrays, the shared mesh, and every compiled
        sweep keyed on (mode, per, nb) are reused from the old plan.
        Returns None when the repacked buckets would not fit the old
        layout (per/nb drift — not a weight-only successor)."""
        self._check(plan, b)
        shards = build_edge_shards_cols(b.src, b.dst, b.w, plan.n_pad,
                                        self.n_shards, self.mode)
        if shards["mode"] != plan.mode or int(shards["per"]) != plan.per \
                or int(shards.get("nb", 0)) != plan.nb:
            return None
        e = plan.eargs

        def put_w(w):
            return self._put(w, self._edges, b.dtype)

        if plan.mode == "replicated":
            eargs = (e[0], e[1], put_w(shards["w"]))
        else:
            eargs = (e[0], e[1], put_w(shards["a"]["w"]),
                     e[3], e[4], put_w(shards["h"]["w"]))
        return ShardedPlan(key=key or b.structure_key(), backend=self.name,
                           n_pad=plan.n_pad, mesh=plan.mesh, mode=plan.mode,
                           n_shards=plan.n_shards, per=plan.per, nb=plan.nb,
                           eargs=eargs)

    def _vector_layout(self, plan: ShardedPlan, h0, ca, ch, m, dtype):
        """Per-batch device layout of the (n_pad, V) vectors.

        dual_blocked pads node rows to nb*S >= n_pad — non-pow2 device
        counts get dead extra rows (zero weights/mask/h0), like the
        service's pad row — and iterates h in (S, nb, V) blocked form,
        block s on device s.
        """
        rep = self._replicated
        if plan.mode == "replicated":
            return tuple(self._put(x, rep, dtype) for x in (h0, ca, ch, m))
        nb = plan.nb
        n_rows, v = np.shape(h0)
        rows = ((0, nb * plan.n_shards - n_rows), (0, 0))
        h0, ca, ch, m = (np.pad(np.asarray(x), rows) for x in (h0, ca, ch, m))
        return (self._put(h0.reshape(plan.n_shards, nb, v), self._blocked,
                          dtype),
                self._put(ca, rep, dtype), self._put(ch, rep, dtype),
                self._put(m, rep, dtype))

    def sweep(self, plan: ShardedPlan, b: SweepBatch):
        self._check(plan, b)
        n_pad, v = b.h0.shape
        h0, ca, ch, m = self._vector_layout(plan, b.h0, b.ca, b.ch, b.mask,
                                            b.dtype)
        fn = _sharded_converge(plan.mesh, plan.mode, n_pad, plan.per, v,
                               b.max_iter, b.dtype, self.axes,
                               rank_k=int(b.rank_k),
                               stable_sweeps=int(b.stable_sweeps),
                               bulk_dtype=b.ladder_key() or None)
        with jax.set_mesh(plan.mesh):
            h, a, conv, res = fn(h0, ca, ch, m, plan.eargs, b.tol,
                                 b.bulk_tol())
        h = np.asarray(h).reshape(-1, v)[:n_pad]
        a = np.asarray(a).reshape(-1, v)[:n_pad]
        return h, a, np.asarray(conv), np.asarray(res)

    def measure_wire_bytes(self, n_pad: int, v: int, src, dst, w,
                           dtype=jnp.float64) -> float:
        """Compile ONE sweep at these shapes and measure per-device ring
        wire bytes from the optimized HLO (the bench/test ladder probe)."""
        zeros = np.zeros((n_pad, v))
        plan = self.plan(SweepBatch(
            h0=zeros, src=src, dst=dst, w=w, ca=zeros, ch=zeros, mask=zeros,
            tol=0.0, max_iter=1, dtype=dtype))
        h0, ca, ch, m = self._vector_layout(plan, zeros, zeros, zeros,
                                            zeros, dtype)
        smapped = make_dist_hits_sweep_cols(plan.mesh, self.mode, n_pad,
                                            axes=self.axes)
        with jax.set_mesh(plan.mesh):
            compiled = jax.jit(smapped).lower(h0, ca, ch, m,
                                              *plan.eargs).compile()
        return wire_bytes_from_collectives(
            collective_bytes(compiled.as_text())["by_kind"], self.n_shards)


# --------------------------------------------------------------------- bsr


class BsrSweepBackend(SweepBackend):
    """Pallas block-sparse path for the dense-block regime.

    The union subgraph is renumbered by ``core.reordering``'s blocking
    permutation (non-dangling pages first, degree-descending) so structural
    nonzeros cluster into dense (bs x bs) blocks, then each half-step is one
    ``bsr_scaled_matvec`` with the column's induced diagonal fused into the
    block matmul prologue. The convergence loop is fused on-device by
    default (``kernels.bsr_converge_cols``: ``lax.while_loop`` with the
    tolerance check in the carry — one dispatch per batch, the TPU serving
    path); ``fused=False`` keeps the host-driven loop, which pays a
    host<->device round trip per iteration and serves as the fused loop's
    parity reference.
    """

    name = "bsr"

    def __init__(self, bs: int = 128, interpret: Optional[bool] = None,
                 fused: bool = True):
        self.bs = bs
        self.interpret = interpret
        self.fused = fused

    def plan_params(self) -> tuple:
        return (self.bs,)

    def plan(self, b: SweepBatch, key: str = "") -> BsrPlan:
        """Blocking permutation + both BSR structures — the expensive
        host-side layout work (two block builds) repeat batches skip."""
        check_bsr_dtype(b.dtype, self.interpret)
        n_pad = b.h0.shape[0]
        real = np.asarray(b.w) != 0  # drop sentinel padding edges
        src, dst = np.asarray(b.src)[real], np.asarray(b.dst)[real]
        w = np.asarray(b.w)[real]
        perm = blocking_permutation(src, dst, n_pad)
        inv = np.empty_like(perm)
        inv[perm] = np.arange(n_pad, dtype=np.int32)
        g = Graph(n_pad, inv[src], inv[dst])
        bs = min(self.bs, n_pad)
        accum = b.dtype if np.dtype(b.dtype) == np.float64 else jnp.float32
        lt = DeviceBSR.build(g, bs, transpose=True, dtype=b.dtype, values=w)
        lfwd = DeviceBSR.build(g, bs, transpose=False, dtype=b.dtype,
                               values=w)
        lt_lo = lfwd_lo = None
        if b.bulk_dtype is not None:
            # ladder: low-precision operator copies share the idx arrays;
            # only the block values are cast (the bulk phase's working set)
            bd = np.dtype(b.bulk_dtype)
            lt_lo = DeviceBSR(lt.blocks.astype(bd), lt.idx, bs,
                              lt.n_nodes, lt.n_pad)
            lfwd_lo = DeviceBSR(lfwd.blocks.astype(bd), lfwd.idx, bs,
                                lfwd.n_nodes, lfwd.n_pad)
        return BsrPlan(
            key=key or b.structure_key(), backend=self.name, n_pad=n_pad,
            perm=perm, inv=inv,
            perm_dev=jnp.asarray(perm), inv_dev=jnp.asarray(inv),
            lt=lt, lfwd=lfwd, bs=bs, accum_dtype=accum,
            lt_lo=lt_lo, lfwd_lo=lfwd_lo)

    def plan_arrays(self, plan: BsrPlan):
        arrays = {"perm": np.asarray(plan.perm), "inv": np.asarray(plan.inv),
                  "lt_blocks": np.asarray(plan.lt.blocks),
                  "lt_idx": np.asarray(plan.lt.idx),
                  "lfwd_blocks": np.asarray(plan.lfwd.blocks),
                  "lfwd_idx": np.asarray(plan.lfwd.idx)}
        # the lo operator copies are NOT persisted — they're a cast of the
        # full-precision blocks, rebuilt from them at restore
        bulk = "" if plan.lt_lo is None else str(np.dtype(plan.lt_lo.blocks.dtype))
        return arrays, {"n_pad": int(plan.n_pad), "bs": int(plan.bs),
                        "bsr_n_nodes": int(plan.lt.n_nodes),
                        "bsr_n_pad": int(plan.lt.n_pad),
                        "accum": str(np.dtype(plan.accum_dtype)),
                        "bulk": bulk}

    def plan_restore(self, key: str, arrays, meta) -> BsrPlan:
        bs = int(meta["bs"])
        if bs != min(self.bs, int(meta["n_pad"])):
            raise ValueError(f"spilled plan blocked at bs={bs}, "
                             f"backend wants {self.bs}")
        nn, npd = int(meta["bsr_n_nodes"]), int(meta["bsr_n_pad"])
        lt = DeviceBSR(jnp.asarray(arrays["lt_blocks"]),
                       jnp.asarray(arrays["lt_idx"]), bs, nn, npd)
        lfwd = DeviceBSR(jnp.asarray(arrays["lfwd_blocks"]),
                         jnp.asarray(arrays["lfwd_idx"]), bs, nn, npd)
        accum = (np.dtype(meta["accum"]) if meta["accum"] == "float64"
                 else jnp.float32)
        lt_lo = lfwd_lo = None
        if meta.get("bulk"):
            bd = np.dtype(meta["bulk"])
            lt_lo = DeviceBSR(lt.blocks.astype(bd), lt.idx, bs, nn, npd)
            lfwd_lo = DeviceBSR(lfwd.blocks.astype(bd), lfwd.idx, bs, nn,
                                npd)
        perm, inv = arrays["perm"], arrays["inv"]
        return BsrPlan(key=key, backend=self.name, n_pad=int(meta["n_pad"]),
                       perm=perm, inv=inv, perm_dev=jnp.asarray(perm),
                       inv_dev=jnp.asarray(inv), lt=lt, lfwd=lfwd, bs=bs,
                       accum_dtype=accum, lt_lo=lt_lo, lfwd_lo=lfwd_lo)

    def patch(self, plan: BsrPlan, b: SweepBatch,
              key: str = "") -> Optional[BsrPlan]:
        """Weight-only update keeping the blocking permutation and block
        layout: re-scatter the new edge values into the existing idx
        tables (``kernels.ops.bsr_revalue``) and rebuild only the device
        block arrays. The permutation, index tables, and kernel grid all
        survive, so a patched plan hits the same compiled sweep. Returns
        None when any retained edge falls outside the old block layout
        (e.g. a weight moved off zero on an edge the old plan dropped) —
        the caller replans."""
        self._check(plan, b)
        real = np.asarray(b.w) != 0  # drop sentinel padding edges
        src, dst = np.asarray(b.src)[real], np.asarray(b.dst)[real]
        w = np.asarray(b.w)[real]
        inv = np.asarray(plan.inv)
        ps, pd = inv[src], inv[dst]
        bs = plan.bs
        # lt was built transposed (Graph.reverse swaps endpoints)
        lt_blocks = bsr_revalue(plan.lt.idx, bs, plan.lt.n_pad, pd, ps, w)
        lfwd_blocks = bsr_revalue(plan.lfwd.idx, bs, plan.lfwd.n_pad,
                                  ps, pd, w)
        if lt_blocks is None or lfwd_blocks is None:
            return None
        lt = DeviceBSR(jnp.asarray(lt_blocks, b.dtype), plan.lt.idx, bs,
                       plan.lt.n_nodes, plan.lt.n_pad)
        lfwd = DeviceBSR(jnp.asarray(lfwd_blocks, b.dtype), plan.lfwd.idx,
                         bs, plan.lfwd.n_nodes, plan.lfwd.n_pad)
        lt_lo = lfwd_lo = None
        if b.bulk_dtype is not None:
            bd = np.dtype(b.bulk_dtype)
            lt_lo = DeviceBSR(lt.blocks.astype(bd), lt.idx, bs,
                              lt.n_nodes, lt.n_pad)
            lfwd_lo = DeviceBSR(lfwd.blocks.astype(bd), lfwd.idx, bs,
                                lfwd.n_nodes, lfwd.n_pad)
        return BsrPlan(
            key=key or b.structure_key(), backend=self.name,
            n_pad=plan.n_pad, perm=plan.perm, inv=plan.inv,
            perm_dev=plan.perm_dev, inv_dev=plan.inv_dev,
            lt=lt, lfwd=lfwd, bs=bs, accum_dtype=plan.accum_dtype,
            lt_lo=lt_lo, lfwd_lo=lfwd_lo)

    def sweep(self, plan: BsrPlan, b: SweepBatch):
        self._check(plan, b)
        # batch vectors upload unpermuted; the blocking permutation is an
        # on-device gather (entry) / inverse gather (exit) — no host
        # fancy-indexing per batch (the ROADMAP on-device-permute item)
        ca = jnp.asarray(b.ca, b.dtype)
        ch = jnp.asarray(b.ch, b.dtype)
        m = jnp.asarray(b.mask, b.dtype)
        h = jnp.asarray(b.h0, b.dtype)
        if self.fused:
            h, a, conv, res = bsr_converge(
                plan.lt, plan.lfwd, h, ca, ch, m, b.tol, b.max_iter,
                self.interpret, plan.accum_dtype,
                perm=plan.perm_dev, inv=plan.inv_dev,
                rank_k=int(b.rank_k), stable_sweeps=int(b.stable_sweeps),
                lt_lo=plan.lt_lo, lfwd_lo=plan.lfwd_lo,
                bulk_tol=b.bulk_tol(), bulk_dtype=b.ladder_key() or None)
            return (np.asarray(h), np.asarray(a), np.asarray(conv),
                    np.asarray(res))
        # host-driven reference loop: one residual round trip per sweep
        # (entry/exit permutation still on device, once per batch)
        perm_d, inv_d = plan.perm_dev, plan.inv_dev
        h, ca, ch, m = (jnp.take(x, perm_d, axis=0) for x in (h, ca, ch, m))
        v = b.h0.shape[1]
        k_eff = min(int(b.rank_k), b.h0.shape[0]) if b.rank_k else 0

        def host_loop(lt_op, lfwd_op, hh, cah, chh, mh, stop_tol, k, accum):
            # rank-stability state is loop-local: it resets at the ladder's
            # phase boundary, mirroring the fused kernel exactly
            if k_eff:
                top_prev = np.full((v, k_eff), -1, np.int64)
                stab = np.zeros(v, np.int64)
            conv = np.full(v, -1, np.int32)
            while k < b.max_iter and (conv < 0).any():
                a = bsr_matvec(lt_op, hh, chh, self.interpret, accum) * mh
                h_new = bsr_matvec(lfwd_op, a, cah, self.interpret,
                                   accum) * mh
                h_new = normalize_l1(h_new, axis=0)
                delta = np.asarray(jnp.sum(jnp.abs(h_new - hh), axis=0))
                stop = delta <= stop_tol
                if k_eff:
                    # numpy mirror of the fused loop's rank-stability stop;
                    # stable argsort of -a == lax.top_k's lowest-index ties
                    top = np.argsort(-np.asarray(a), axis=0,
                                     kind="stable")[:k_eff].T
                    same = (top == top_prev).all(axis=1)
                    stab = np.where(same, stab + 1, 0)
                    stop = stop | (stab >= int(b.stable_sweeps))
                    top_prev = top
                k += 1
                conv = np.where((conv < 0) & stop, k, conv)
                hh = h_new
            return hh, k, conv

        k = 0
        if plan.lt_lo is not None:
            bd = plan.lt_lo.blocks.dtype
            h_lo, k, _ = host_loop(plan.lt_lo, plan.lfwd_lo, h.astype(bd),
                                   ca.astype(bd), ch.astype(bd),
                                   m.astype(bd), b.bulk_tol(), k,
                                   jnp.float32)
            h = h_lo.astype(b.dtype)
        h, k, conv = host_loop(plan.lt, plan.lfwd, h, ca, ch, m, b.tol, k,
                               plan.accum_dtype)
        conv = np.where(conv < 0, k, conv)
        # finalize + certificate: one extra full-precision sweep
        a = bsr_matvec(plan.lt, h, ch, self.interpret, plan.accum_dtype) * m
        h2 = normalize_l1(bsr_matvec(plan.lfwd, a, ca, self.interpret,
                                     plan.accum_dtype) * m, axis=0)
        res = np.asarray(jnp.sum(jnp.abs(h2 - h), axis=0))
        a = normalize_l1(a, axis=0)
        return (np.asarray(jnp.take(h, inv_d, axis=0)),
                np.asarray(jnp.take(a, inv_d, axis=0)), conv, res)


# ------------------------------------------------------- selection/factory


def select_backend(n_union: int, e_union: int,
                   n_devices: Optional[int] = None,
                   pallas_compiled: Optional[bool] = None, *,
                   dtype) -> str:
    """The ``auto`` heuristic: pick a backend from subgraph density,
    device count and sweep dtype.

    Multi-device meshes shard once the union subgraph carries enough edges
    to amortize per-sweep collectives; single-device dense-block subgraphs
    take the Pallas BSR path when it actually compiles (TPU — interpreter
    mode would serve slower than the XLA dense path) and the sweep dtype
    is one Mosaic has (no f64); everything else stays dense.
    """
    if n_devices is None:
        n_devices = len(jax.devices())
    if pallas_compiled is None:
        pallas_compiled = not resolve_interpret(None)
    if n_devices > 1 and e_union >= _SHARD_MIN_EDGES:
        return "sharded"
    if pallas_compiled and np.dtype(dtype) != np.float64 \
            and e_union >= _BSR_MIN_EDGES_PER_NODE * max(n_union, 1):
        return "bsr"
    return "dense"


def check_bsr_dtype(dtype, interpret: Optional[bool]):
    """Refuse an f64 sweep on compiled Pallas: Mosaic has no float64, so
    such a batch would fail at its first compile. Interpret mode (the CPU
    default) runs f64 as it always has."""
    if np.dtype(dtype) == np.float64 and not resolve_interpret(interpret):
        raise ValueError(
            "backend 'bsr' cannot sweep float64 with compiled Pallas "
            "(Mosaic has no f64): use dtype=float32 (optionally with "
            "sweep_dtype='bf16'), or backend 'dense' or 'auto'")


def make_backend(kind: str, *, shard_mode: str = "dual_blocked",
                 shard_devices: Optional[int] = None, bsr_block: int = 128,
                 interpret: Optional[bool] = None,
                 bsr_fused: bool = True) -> SweepBackend:
    if kind == "dense":
        return DenseSweepBackend()
    if kind == "sharded":
        return ShardedSweepBackend(mode=shard_mode, n_devices=shard_devices)
    if kind == "bsr":
        return BsrSweepBackend(bs=bsr_block, interpret=interpret,
                               fused=bsr_fused)
    raise ValueError(f"unknown backend {kind!r} (want one of {BACKENDS})")
