"""Query-focused HITS ranking service (the ROADMAP serving scenario).

Serves per-query accelerated-HITS rankings over focused subgraphs:

1. **Focus** — each query's root set expands to a base set and induced
   subgraph (``graph.subgraph``), shrinking the iteration space from the
   crawl to a few hundred pages (Dong et al.'s lumping motivation, done
   structurally).
2. **Batch** — up to V concurrent queries run as the V columns of ONE
   multi-vector accelerated-HITS iteration over the union subgraph
   (``core.hits.hits_sweep_cols``): per-column induced weights + masks make
   column j mathematically identical to running ``accel_hits`` on query
   j's own subgraph, while the edge traversal (the hot loop) is shared.
3. **Cache** — converged authority/hub vectors are LRU-cached per root-set
   hash; repeat queries are served from cache, and overlapping queries
   warm-start from the last converged scores instead of the uniform
   vector (paper §5: accelerated vectors as warm starts; Peserico &
   Pretto: query-time HITS can converge slowly, so the saved sweeps are
   the point).

Shapes are padded to power-of-two buckets so the jitted convergence loop
compiles once per bucket, not once per query mix.

The convergence loop itself is pluggable (see ``serve.backends``): the
``dense`` single-device path, the mesh-``sharded`` path over the
``sparse.dist`` edge-sharding ladder, and the Pallas ``bsr`` block-sparse
path all consume the same padded batch and match each other to <=1e-10 L1.
Two stopping refinements ride on every backend: a **rank-stability early
exit** (``rank_k > 0``: a column stops once its top-k authority ordering
has held ``stable_sweeps`` sweeps — Peserico & Pretto's rank-before-score
convergence as a serving feature) and a **precision ladder**
(``sweep_dtype``: bulk sweeps at bf16/fp32, then an f64 polish to
``polish_tol`` whose one-extra-sweep residual certificate publishes on
``QueryResult.residual``).

Execution is staged (see ``serve.pipeline``): every batch — whether it
came from this synchronous ``rank()`` or from the SLA-aware queued
frontend (``serve.queue.RankQueue`` via ``.queue()``: priority classes,
per-request deadlines, shedding under overload) — runs
assemble → plan → sweep → publish through one ``ServePipeline``, which at
``pipeline_depth >= 2`` overlaps the next batch's host work with the
current batch's device sweep.

**Live graph.** The served graph is versioned (``GraphVersion``): version
0 at construction, one more for each delta ``apply_edge_delta`` rolls
in. A roll builds the next version off the service lock while batches
keep sweeping, then swaps it in atomically; admission never closes. Each
batch is assembled on one version, and every ``QueryResult`` carries the
``graph_version`` it is exact for. A request submitted after a roll's
acknowledgement is answered at that version or a later one, and an
answer computed on a version that a roll has since replaced is published
but never cached.

Every layer counts into one typed ``serve.telemetry.MetricsRegistry``
(``self.telemetry``; the legacy ``stats`` dict is a live alias view over
it). ``docs/ARCHITECTURE.md`` is the end-to-end tour of this stack;
``docs/OPERATIONS.md`` is the operator runbook (every metric, the
health/stats endpoint, drain semantics, spill GC).
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict, deque
from contextlib import nullcontext
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..graph.structure import Graph
from ..graph.subgraph import FocusedSubgraph, SubgraphExtractor
from .backends import SweepBackend, SweepBatch, make_backend, select_backend
from .delta import EdgeDelta, EdgeTable, apply_to_graph
from .plans import PlanCache, SweepPlan, topology_key
from .telemetry import span


@dataclasses.dataclass
class RankServiceConfig:
    v_max: int = 8             # queries batched per traversal (the V columns)
    out_cap: int = 32          # base-set expansion caps (per root)
    in_cap: int = 32
    tol: float = 1e-10
    max_iter: int = 1000
    # rank-stability early exit (Peserico & Pretto: score convergence can
    # lag rank convergence arbitrarily): with rank_k > 0 a column also
    # stops once its top-rank_k authority ordering has been unchanged for
    # stable_sweeps consecutive sweeps. 0 keeps exact-residual stopping
    # (bit-identical to the legacy loop on every backend).
    rank_k: int = 0
    stable_sweeps: int = 2
    cache_size: int = 512      # LRU entries (root-set hash -> scores)
    warm_min_overlap: float = 0.5  # min score coverage to warm-start
    dtype: object = jnp.float64
    # precision ladder (serve.backends): a non-empty sweep_dtype ("bf16" |
    # "fp32" | "f64" and spellings thereof) runs the bulk of convergence
    # sweeps at that dtype, then polishes at the full sweep dtype to
    # polish_tol (None: the configured tol) and publishes the residual
    # certificate on QueryResult.residual. "" keeps the single-phase loop.
    sweep_dtype: str = ""
    polish_tol: Optional[float] = None
    backend: str = "dense"     # dense | sharded | bsr | auto (see backends)
    shard_mode: str = "dual_blocked"   # sharded: replicated | dual_blocked
    shard_devices: Optional[int] = None  # sharded: device count (None: all)
    bsr_block: int = 128       # bsr: block size (MXU-aligned on TPU)
    interpret: Optional[bool] = None   # bsr: Pallas interpret override
    bsr_fused: bool = True     # bsr: fused on-device convergence loop
    # plan cache (serve.plans): LRU of per-union-subgraph structural
    # layouts (edge shards, BSR blockings, device edge lists) so repeat
    # root sets skip host-side rebuilds; <= 0 disables
    plan_cache_size: int = 64
    # plan-time lumped sweep reduction (serve.plans.lump_batch — Dong,
    # Feng & You): "on" shrinks every assembled batch before planning and
    # sweeping (isolated rows dropped, duplicate-pattern classes collapsed
    # to multiplicity-weighted representatives) and exactly unlumps the
    # published vectors; "auto" applies it only when the reduction removes
    # at least plans.LUMP_AUTO_MIN_RATIO of the union's live rows; "off"
    # (default) is bit-identical to the pre-lumping path
    lumping: str = "off"
    # staged dispatch pipeline (serve.pipeline.ServePipeline): number of
    # batches in flight. 1 = serial (assemble(j) sees publish(j-1));
    # >= 2 overlaps batch j's host assemble/plan with batch j-1's device
    # sweep (assemble(j) deterministically sees publish(j-depth))
    pipeline_depth: int = 2
    # async micro-batching frontend (serve.queue.RankQueue / .queue()):
    deadline_ms: float = 5.0   # max extra latency batching may add
    queue_depth: Optional[int] = None  # max distinct pending (None: 4*v_max)
    # SLA admission: submits with priority >= shed_priority are
    # best-effort — under overload they resolve with status "shed"
    # instead of blocking guaranteed traffic (classes < shed_priority)
    shed_priority: int = 1
    # restart-survivable cache spill (serve.spill.CacheSpill):
    spill_dir: Optional[str] = None    # None: in-process cache only
    spill_policy: str = "all"  # all: every converged entry | evict: LRU only
    # spill generation GC: newest step_* generations kept per entry
    # stream; init (and queue.drain) compacts the whole spill dir to this
    spill_keep_generations: int = 1


@dataclasses.dataclass
class QueryResult:
    roots: np.ndarray       # the (deduped, sorted) root set
    nodes: np.ndarray       # global ids of the focused subgraph
    authority: np.ndarray   # L1-normalized over ``nodes``
    hub: np.ndarray
    iters: int              # sweeps to convergence (0 for a cache hit)
    status: str             # "hit" | "warm" | "cold" | "shed" (queue only)
    key: str                # root-set hash (the cache key)
    # residual certificate: ‖sweep(h) − h‖₁ from one extra full-precision
    # sweep at the published h — the provable convergence bound the
    # precision ladder (and the legacy loop) publishes. None only for
    # results cached before certificates existed (old spill records).
    residual: Optional[float] = None
    # the graph version the answer is exact for: its batch's version (a
    # cache hit: the version current at the probe, as every cached entry
    # is valid for the current version)
    graph_version: int = 0

    def topk(self, k: int = 10):
        """Top-k (global node id, authority score) pairs."""
        order = np.argsort(-self.authority)[:k]
        return [(int(self.nodes[i]), float(self.authority[i]))
                for i in order]


@dataclasses.dataclass(frozen=True)
class GraphVersion:
    """One version of the served graph, as a batch is assembled on it:
    the graph, its extractor and its edge-weight table (None until the first
    delta). ``version`` counts the deltas applied since the
    service was built."""

    version: int
    g: Graph
    extractor: SubgraphExtractor
    edge_table: Optional[EdgeTable] = None


@dataclasses.dataclass
class _CacheEntry:
    nodes: np.ndarray
    authority: np.ndarray
    hub: np.ndarray
    residual: Optional[float] = None  # certificate at converge time


class RankService:
    """Batched, cached, warm-starting query-ranking front end over one graph."""

    def __init__(self, g: Graph, config: Optional[RankServiceConfig] = None):
        self.cfg = config or RankServiceConfig()
        # without jax_enable_x64 a float64 request silently runs fp32, whose
        # residual floor (~1e-7) never reaches the default tol — every cold
        # query would spin to max_iter. Clamp tol to what the effective
        # dtype can resolve and say so.
        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # x64-truncation noise
            eff = jnp.zeros((), self.cfg.dtype).dtype
        self._dtype = eff
        from .backends import (check_bsr_dtype, dtype_floor,
                               resolve_sweep_dtype)
        min_tol = dtype_floor(eff)
        if self.cfg.tol < min_tol:
            warnings.warn(
                f"RankService tol={self.cfg.tol:g} is below the {eff} "
                f"residual floor (x64 disabled?); clamping to {min_tol:g}",
                stacklevel=2)
            self.cfg = dataclasses.replace(self.cfg, tol=min_tol)
        # precision ladder: resolve/validate once; the shared switch-over
        # criterion (backends.bulk_stop_tol) runs off _bulk_dtype at sweep
        # time. A ladder whose bulk dtype IS the sweep dtype degenerates to
        # the single-phase loop — normalize it to None so the trace (and
        # the plan-cache key) is bit-identical to a ladder-free service.
        bulk = resolve_sweep_dtype(self.cfg.sweep_dtype)
        if bulk is not None and bulk == np.dtype(eff):
            bulk = None
        if bulk is not None and \
                jnp.finfo(bulk).eps < float(jnp.finfo(eff).eps):
            raise ValueError(
                f"sweep_dtype {bulk} is higher precision than the sweep "
                f"dtype {eff} — the ladder's bulk phase must be the cheap "
                f"one")
        self._bulk_dtype = bulk
        polish = self.cfg.polish_tol
        if polish is None:
            polish = self.cfg.tol
        else:
            polish = float(polish)
            if polish <= 0:
                raise ValueError(f"polish_tol must be > 0, got {polish}")
            if polish < min_tol:
                warnings.warn(
                    f"polish_tol={polish:g} is below the {eff} residual "
                    f"floor; clamping to {min_tol:g}", stacklevel=2)
                polish = min_tol
        self._polish_tol = polish
        if self.cfg.backend not in ("dense", "sharded", "bsr", "auto"):
            raise ValueError(f"unknown backend {self.cfg.backend!r}")
        if self.cfg.backend == "bsr":
            check_bsr_dtype(eff, self.cfg.interpret)
        if self.cfg.rank_k < 0:
            raise ValueError(f"rank_k must be >= 0, got {self.cfg.rank_k}")
        if self.cfg.stable_sweeps < 1:
            raise ValueError(
                f"stable_sweeps must be >= 1, got {self.cfg.stable_sweeps}")
        if self.cfg.spill_policy not in ("all", "evict"):
            raise ValueError(f"unknown spill policy {self.cfg.spill_policy!r}")
        if self.cfg.lumping not in ("off", "on", "auto"):
            raise ValueError(f"unknown lumping mode {self.cfg.lumping!r} "
                             f"(want off | on | auto)")
        # "off" normalizes to None (mirroring the ladder) so the disabled
        # path touches no lumping code and stays bit-identical
        self._lumping = None if self.cfg.lumping == "off" else self.cfg.lumping
        # the live graph version; replaced whole, under the lock, by a roll
        self._live = GraphVersion(0, g, SubgraphExtractor(
            g, self.cfg.out_cap, self.cfg.in_cap))
        # one roll at a time: each builds on the version before it
        self._roll_lock = threading.Lock()
        self._backends: Dict[str, SweepBackend] = {}
        self._cache: OrderedDict[str, _CacheEntry] = OrderedDict()
        self._plans = PlanCache(self.cfg.plan_cache_size)
        # last converged scores per global node — the warm-start table
        self._warm_h = np.zeros(g.n_nodes)
        self._warm_seen = np.zeros(g.n_nodes, bool)
        # guards every mutable serving structure (stats, vector cache,
        # warm table, plan cache): pipeline stages read/write them from
        # the prepare worker and the driving thread concurrently
        self._lock = threading.RLock()
        # one typed registry per service (serve.telemetry); the pipeline
        # shares it. The legacy ``stats`` dict-of-ints surface stays as a
        # live alias view so existing readers/mutators are unchanged.
        from .telemetry import LabeledView, LegacyStatsDict, MetricsRegistry
        reg = self.telemetry = MetricsRegistry()
        self.stats = LegacyStatsDict({
            "queries": reg.counter("service.queries"),
            "batches": reg.counter("service.batches"),
            "hit": reg.counter("service.cache.hit"),
            "warm": reg.counter("service.cache.warm"),
            "cold": reg.counter("service.cache.cold"),
            "sweeps": reg.counter("service.sweeps"),
            "backend_batches": LabeledView(reg, "service.backend.batches"),
            "plan_hits": reg.counter("service.plan.hits"),
            "plan_misses": reg.counter("service.plan.misses"),
            "plan_evictions": reg.counter("service.plan.evictions"),
            "plan_restored": reg.counter("service.plan.restored"),
            "plan_spilled": reg.counter("service.plan.spilled"),
            "spill_writes": reg.counter("service.spill.writes"),
            "spill_hits": reg.counter("service.spill.hits"),
            "spill_restored": reg.counter("service.spill.restored"),
            "spill_gc_removed": reg.counter("service.spill.gc_removed"),
        })
        # non-legacy families, registered eagerly so names() (and the
        # runbook consistency test) see the full set before traffic does
        self._m_sweep_iters = reg.histogram("service.sweep.iters")
        # swept union subgraph size per batch, before padding: what sizes
        # the device program (n_pad, e_pad buckets) and the plan
        self._m_union_nodes = reg.histogram("service.union.nodes")
        self._m_union_edges = reg.histogram("service.union.edges")
        for reason in ("residual", "rank_stable", "max_iter"):
            reg.counter("service.exit", reason)
        if self.cfg.backend != "auto":  # auto resolves per batch
            reg.counter("service.backend.batches", self.cfg.backend)
        self._m_ladder = reg.counter("service.ladder.bulk_batches")
        self._m_spill_read = reg.histogram("service.spill.read_ms")
        self._m_spill_write = reg.histogram("service.spill.write_ms")
        reg.gauge("service.cache.entries")
        reg.gauge("service.plan_cache.entries")
        # plan-time lumping (serve.plans.lump_batch): live rows removed per
        # swept batch and the per-batch reduction ratio (observed only for
        # batches the reduction actually applied to)
        self._m_lumped_nodes = reg.counter("service.plan.lumped_nodes")
        self._m_reduction_ratio = reg.histogram(
            "service.plan.reduction_ratio")
        # live edge-delta rolls (apply_edge_delta / the lazy plan patching
        # it arms): plans value-patched (labeled by the backend that
        # patched) vs fully replanned, result-cache entries invalidated,
        # the roll's spans, what the deltas carried, the live version, and
        # answers of a replaced version kept out of the cache
        from .backends import BACKENDS
        for b in BACKENDS:
            reg.counter("service.delta.patched", b)
        self._m_delta_replanned = reg.counter("service.delta.replanned")
        self._m_delta_invalidated = reg.counter("service.delta.invalidated")
        self._m_delta = {stage: reg.histogram(f"service.delta.{stage}_ms")
                         for stage in ("roll", "apply", "extract", "swap")}
        self._m_links_added = reg.counter("delta.links_added")
        self._m_links_removed = reg.counter("delta.links_removed")
        self._m_pages_added = reg.counter("delta.pages_added")
        self._m_stale_uncached = reg.counter("service.stale_uncached")
        self._m_version = reg.gauge("service.graph_version")
        # (version, stage, t0, t1) of each applied roll's spans, on
        # time.perf_counter, like ServePipeline.trace
        self.delta_trace = deque(maxlen=256)
        # weight-blind plan index: topo key -> the newest full cache key
        # with that topology, so a post-reweight batch can patch the
        # predecessor plan instead of rebuilding (see _plan_for)
        self._topo_index: Dict[tuple, tuple] = {}
        self._spill = None
        self._plan_spill = None
        self._spill_pending: list = []  # deferred writes (see _drain_spill)
        self._spill_io_lock = threading.Lock()  # serializes disk writes
        if self.cfg.spill_dir is not None:
            from .spill import CacheSpill, PlanSpill
            keep = self.cfg.spill_keep_generations
            self._spill = CacheSpill(self.cfg.spill_dir,
                                     keep_generations=keep)
            self._plan_spill = PlanSpill(self.cfg.spill_dir,
                                         keep_generations=keep)
            self._restore_spilled()
            self.gc_spill()  # compact stale generations + crash droppings
        from .pipeline import ServePipeline
        self.pipeline = ServePipeline(self, depth=self.cfg.pipeline_depth)

    # -- the live graph version -------------------------------------------

    @property
    def g(self) -> Graph:
        return self._live.g

    @property
    def extractor(self) -> SubgraphExtractor:
        return self._live.extractor

    @property
    def graph_version(self) -> int:
        return self._live.version

    def queue(self, **kw):
        """An async micro-batching frontend over this service (the config's
        ``deadline_ms``/``queue_depth`` unless overridden)."""
        from .queue import RankQueue
        kw.setdefault("deadline_ms", self.cfg.deadline_ms)
        # 0 and None both mean "the 4*v_max default" (configs use 0)
        kw.setdefault("max_pending", self.cfg.queue_depth or None)
        kw.setdefault("shed_priority", self.cfg.shed_priority)
        return RankQueue(self, **kw)

    # -- backends ---------------------------------------------------------

    def _backend_for(self, n_union: int, e_union: int) -> SweepBackend:
        """Resolve the configured (or ``auto``-selected) sweep backend.

        Instances are cached per kind: ``auto`` may route small union
        subgraphs dense and large ones sharded within one service without
        rebuilding meshes or BSR state machinery.
        """
        kind = self.cfg.backend
        if kind == "auto":
            from ..kernels import resolve_interpret
            kind = select_backend(
                n_union, e_union, n_devices=self.cfg.shard_devices,
                pallas_compiled=not resolve_interpret(self.cfg.interpret),
                dtype=self._dtype)
        be = self._backends.get(kind)
        if be is None:
            be = make_backend(kind, shard_mode=self.cfg.shard_mode,
                              shard_devices=self.cfg.shard_devices,
                              bsr_block=self.cfg.bsr_block,
                              interpret=self.cfg.interpret,
                              bsr_fused=self.cfg.bsr_fused)
            self._backends[kind] = be
        return be

    def _plan_for(self, backend: SweepBackend, batch: SweepBatch) -> SweepPlan:
        """The backend's structural plan for this batch, LRU-cached by
        union-subgraph content hash.

        The hash covers the padded edge structure itself (not just the
        root-set ids), so a mutated graph — same nodes, different edges —
        changes the key and can never be served a stale layout. Repeat and
        overlapping root sets that induce the same union subgraph skip all
        host-side layout rebuilding (edge shards, BSR blocking, device
        transfer).

        With a ``spill_dir``, plans also persist next to the vector spill
        (``serve.spill.PlanSpill``): a cache miss tries the disk copy
        before rebuilding, so a restarted service skips layout rebuilds
        too (``plan_restored``), and every built plan is written through
        (``plan_spilled``).
        """
        skey = batch.structure_key()
        # stopping params AND the precision ladder join the key: a plan
        # reused under a different (rank_k, stable_sweeps) regime must
        # never alias spilled records or future stopping-aware layouts
        # built for another regime, and a ladder plan carries bulk-dtype
        # operator copies (bsr) a ladder-free plan lacks
        stop = (int(batch.rank_k), int(batch.stable_sweeps),
                batch.ladder_key())
        if batch.lump_key:
            # lumped plans must never alias unlumped ones, in memory or on
            # disk: the reduction map's content hash joins the key (and
            # through it the PlanSpill record). Unlumped batches keep the
            # legacy tuple bit-identical.
            stop = stop + ("lump:" + batch.lump_key,)
        key = (backend.name, backend.plan_params(), skey, stop)
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:
                self.stats["plan_hits"] += 1
                return plan
        # weight-blind probe: an edge-weight delta changed skey but not the
        # topology — a same-topology predecessor plan's layout (device edge
        # lists, shard buckets, BSR blocking) can be value-patched instead
        # of rebuilt. The probe is hit/miss-neutral; only a successful
        # patch counts (service.delta.patched), a failed one falls through
        # to the normal rebuild (service.delta.replanned).
        tkey = (backend.name, backend.plan_params(),
                topology_key(batch.src, batch.dst, batch.h0.shape[0],
                             batch.dtype), stop)
        with self._lock:
            old_key = self._topo_index.get(tkey)
            old_plan = (self._plans.peek(old_key)
                        if old_key is not None and old_key != key else None)
        had_predecessor = old_plan is not None
        if old_plan is not None:
            plan = backend.patch(old_plan, batch, skey)
            if plan is not None:
                with self._lock:
                    self._plans.put(key, plan)
                    self._topo_index[tkey] = key
                    self.telemetry.counter("service.delta.patched",
                                           backend.name).inc()
                    self.stats["plan_evictions"] = \
                        self._plans.stats["evictions"]
                self._spill_plan(backend, key, plan)
                return plan
        if self._plan_spill is not None:  # disk before rebuild (restart)
            plan = self._restore_plan(backend, key, skey)
            if plan is not None:
                with self._lock:
                    self._plans.put(key, plan)
                    self._topo_index[tkey] = key
                    self.stats["plan_restored"] += 1
                    self.stats["plan_evictions"] = \
                        self._plans.stats["evictions"]
                return plan
        plan = backend.plan(batch, skey)
        with self._lock:
            self._plans.put(key, plan)
            self._topo_index[tkey] = key
            if len(self._topo_index) > 4 * max(self.cfg.plan_cache_size, 1):
                self._topo_index.clear()  # advisory index; rebuilt by use
            self.stats["plan_misses"] += 1
            if had_predecessor:
                self._m_delta_replanned.inc()
            self.stats["plan_evictions"] = self._plans.stats["evictions"]
        self._spill_plan(backend, key, plan)
        return plan

    def _spill_plan(self, backend: SweepBackend, key: tuple,
                    plan: SweepPlan):
        """Write-through a built/patched plan to the plan spill.

        Durability is strictly optional: a full disk or unserializable
        backend must not fail a batch whose plan is already built and
        cached (TypeError: json-unserializable meta from a backend)."""
        if self._plan_spill is None:
            return
        try:
            arrays, meta = backend.plan_arrays(plan)
            with self._spill_io_lock:  # concurrent same-key builds
                self._plan_spill.put(key, arrays, meta)
            with self._lock:
                self.stats["plan_spilled"] += 1
        except (NotImplementedError, OSError, ValueError, TypeError):
            pass

    def _restore_plan(self, backend: SweepBackend, key: tuple,
                      skey: str) -> Optional[SweepPlan]:
        """A spilled plan for this cache key, rehydrated — or None (absent,
        foreign, corrupt, or mismatched layout params: never crash the
        serving path over a bad disk record, just rebuild)."""
        rec = self._plan_spill.get(key)
        if rec is None:
            return None
        try:
            return backend.plan_restore(skey, *rec)
        except (NotImplementedError, KeyError, ValueError, TypeError):
            return None

    # -- cache ------------------------------------------------------------
    # Disk traffic (spill reads and writes) deliberately lives OUTSIDE the
    # service lock: the pipeline's assemble stage probes the spill after
    # releasing it, and writes queue in ``_spill_pending`` for
    # ``_drain_spill`` — otherwise every checkpoint write would serialize
    # the prepare worker against the publishing thread and erase the
    # host/device overlap the pipeline exists for.

    def _cache_get_mem(self, key: str) -> Optional[_CacheEntry]:
        """In-memory LRU probe only (caller holds the lock). The spill
        fallback for misses is the assemble stage's job, off the lock."""
        e = self._cache.get(key)
        if e is not None:
            self._cache.move_to_end(key)
        return e

    def _admit_spilled(self, key: str, d) -> Optional[_CacheEntry]:
        """Admit a record read back from the spill (caller holds the lock;
        the disk read already happened): validate, count the disk hit,
        restore LRU + warm-table state. No rewrite to disk."""
        live = self._cache_get_mem(key)
        if live is not None:
            # a concurrent run converged this key in the window since the
            # memory probe — the live entry is fresher than the disk one
            return live
        e = self._entry_from_spill(d)
        if e is None:
            return None
        self.stats["spill_hits"] += 1
        self._admit(key, e)
        self._warm_h[e.nodes] = e.hub
        self._warm_seen[e.nodes] = True
        return e

    def _entry_from_spill(self, d) -> Optional[_CacheEntry]:
        """Validate a spilled record (a spill dir pointed at the wrong
        graph must not crash node indexing) -> entry or None."""
        if d is None:
            return None
        nodes = d["nodes"]
        if len(nodes) == 0 or len(d["authority"]) != len(nodes) \
                or len(d["hub"]) != len(nodes) \
                or int(nodes[-1]) >= self.g.n_nodes or int(nodes[0]) < 0:
            return None
        return _CacheEntry(nodes=nodes, authority=d["authority"],
                           hub=d["hub"])

    def _admit(self, key: str, e: _CacheEntry):
        """LRU insert + eviction (spilling evictees keeps them servable;
        the disk write is deferred to ``_drain_spill``)."""
        self._cache[key] = e
        self._cache.move_to_end(key)
        while len(self._cache) > self.cfg.cache_size:
            old_key, old = self._cache.popitem(last=False)
            # under "all" every converged entry was spilled at _cache_put
            if self._spill is not None and self.cfg.spill_policy == "evict":
                self._spill_pending.append((old_key, old.nodes,
                                            old.authority, old.hub))

    def _cache_put(self, key: str, e: _CacheEntry):
        if self._spill is not None and self.cfg.spill_policy == "all":
            self._spill_pending.append((key, e.nodes, e.authority, e.hub))
        self._admit(key, e)

    def _drain_spill(self):
        """Flush deferred spill writes to disk, OUTSIDE the service lock
        (pipeline stages call this after releasing it; the slow half of
        spilling must not block the other thread's cache probes).

        Writes are serialized by the spill IO lock — concurrent runs (a
        sync ``rank`` beside the queue dispatcher) could otherwise race
        ``checkpoint.save`` on the same key's generation — and are
        best-effort: durability failures (disk full, permissions) must
        never fail a batch whose results are already in memory.

        The pending list is taken under the IO lock, which a roll's swap
        also holds: writes taken before a swap land before its generation
        bump (and read as absent after it), and writes still pending at
        the swap are filtered by it, so no pre-delta vector is ever
        written under the new generation.
        """
        if self._spill is None:
            return
        with self._lock:
            if not self._spill_pending:
                return  # don't queue behind another thread's writes
        import time
        written = 0
        with self._spill_io_lock:
            with self._lock:
                pending, self._spill_pending = self._spill_pending, []
            for key, nodes, authority, hub in pending:
                t0 = time.perf_counter()
                try:
                    self._spill.put(key, nodes, authority, hub)
                    written += 1
                except (OSError, ValueError):
                    continue
                self._m_spill_write.observe(
                    (time.perf_counter() - t0) * 1e3)
        if written:
            with self._lock:
                self.stats["spill_writes"] += written

    def _restore_spilled(self):
        """Repopulate the LRU (newest-spilled most recent) and the global
        warm table from a previous process's spill directory."""
        restored = list(self._spill.load_recent(limit=self.cfg.cache_size))
        n = 0
        for key, d in reversed(restored):  # oldest first -> newest ends MRU
            e = self._entry_from_spill(d)
            if e is None:
                continue
            self._admit(key, e)
            self._warm_h[e.nodes] = e.hub
            self._warm_seen[e.nodes] = True
            n += 1
        self.stats["spill_restored"] = n

    def flush_spill(self):
        """Force-spill every in-memory entry (a graceful-shutdown drain for
        ``spill_policy="evict"``; under ``"all"`` everything is already on
        disk)."""
        if self._spill is None:
            raise ValueError("no spill_dir configured")
        self._drain_spill()  # deferred evictee writes aren't in the LRU
        import time
        with self._spill_io_lock:
            # taken under the IO lock, as _drain_spill takes its list: a
            # roll's swap cannot fall between the copy and the writes
            with self._lock:
                entries = [(k, e.nodes, e.authority, e.hub)
                           for k, e in self._cache.items()]
            for key, nodes, authority, hub in entries:
                t0 = time.perf_counter()
                self._spill.put(key, nodes, authority, hub)
                self._m_spill_write.observe(
                    (time.perf_counter() - t0) * 1e3)
        with self._lock:
            self.stats["spill_writes"] += len(entries)

    def gc_spill(self, keep: Optional[int] = None) -> int:
        """Compact the spill directory: prune each entry stream past its
        newest ``spill_keep_generations`` (or ``keep``) ``step_*``
        generations and sweep ``.tmp_*`` crash droppings, for vectors and
        plans both. Runs at init and on queue drain; counted under
        ``service.spill.gc_removed``. No-op (0) without a spill dir."""
        if self._spill is None:
            return 0
        with self._spill_io_lock:
            n = self._spill.gc(keep) + self._plan_spill.gc(keep)
        if n:
            with self._lock:
                self.stats["spill_gc_removed"] += n
        return n

    def clear_result_cache(self):
        """Drop all converged-vector state (LRU entries, pending spill
        writes, the warm-start table) while KEEPING cached plans — the
        bench's warm-plan / cold-vector leg, and a memory valve for
        long-lived services.

        With a spill configured, clearing also bumps the spill's data
        generation: everything on disk was written under the old one and
        now reads as absent, so cleared state stays cleared across both
        the serve path's disk fallback and a restart's restore (it used
        to resurrect from either)."""
        with self._lock:
            self._cache.clear()
            self._spill_pending.clear()  # pre-clear vectors; must not land
            self._warm_h[:] = 0.0
            self._warm_seen[:] = False
        if self._spill is not None:
            with self._spill_io_lock:
                self._spill.bump_data_generation()

    def apply_edge_delta(self, adds=None, removes=None, reweights=None,
                         pages: int = 0) -> dict:
        """Roll a changeset into the running service (live graph mutation
        — no restart, no cold caches, no pause in admission; see
        ``serve.delta``). Returns the acknowledgement: a summary whose
        ``version`` is the new graph version.

        ``adds``: (src, dst) or (src, dst, w) rows; ``removes``: (src,
        dst) rows; ``reweights``: (src, dst, w) rows; ``pages``: new pages,
        ids ``n .. n + pages - 1`` for an ``n``-page graph, which the rows
        may name. Weights must be finite and nonzero (reweight-to-0 is a
        remove).

        The roll (span ``delta.roll``) builds the next version — graph and
        weight table (``delta.apply``), extractor (``delta.extract``,
        structural deltas only) — outside the service lock, while batches
        keep assembling and sweeping on the current one. It then swaps it
        in under the lock (``delta.swap``), with the invalidation below,
        and acknowledges. Queue admission stays open throughout: a batch
        assembled before the swap finishes on the version it was
        assembled on and is stamped with it; its answers are published
        but kept out of the cache (``service.stale_uncached``). A request
        submitted after the acknowledgement is assembled after the swap,
        so it is answered at the new version or a later one. Rolls run
        one at a time.

        What survives, by design:

        * **warm table** — entirely (the tentpole carry-over), grown by
          the new pages: post-delta refreshes warm-start from the
          pre-delta fixed points, which the paper's acceleration premise
          makes converge in a handful of sweeps instead of from uniform.
        * **plans** — weight-only deltas keep every topology, so the next
          lookup value-patches the cached layout (``SweepBackend.patch``
          via the weight-blind topology index; ``service.delta.patched``)
          instead of rebuilding. Structural deltas rebuild only plans
          whose union subgraphs actually changed — untouched unions
          produce byte-identical padded arrays and keep hitting.
        * **cached results outside the delta** — only entries whose node
          set intersects a changed edge's endpoints are invalidated
          (``service.delta.invalidated``); the rest keep serving as hits.

        What cannot survive: pre-delta vectors for touched subgraphs —
        in memory (invalidated at the swap), in flight to disk (pending
        writes dropped), and on disk (the spill's data generation bumps
        in the same critical section, so the disk fallback and
        restart-restore read them as absent; surviving entries re-spill
        under the new generation when ``spill_policy`` is "all").
        """
        with self._roll_lock, span("delta.roll", self._m_delta["roll"]) \
                as sp_roll:
            base = self._live
            delta = EdgeDelta.normalize(adds, removes, reweights,
                                        base.g.n_nodes, pages)
            if delta.empty:
                return {"version": base.version, "structural": False,
                        "pages": 0, "invalidated": 0, "touched_nodes": 0,
                        "data_generation": None, "swap_ms": 0.0,
                        "roll_ms": 0.0}
            with span("delta.apply", self._m_delta["apply"]) as sp_apply:
                new_g, table = apply_to_graph(base.g, base.edge_table, delta)
            spans = [("apply", sp_apply)]
            extractor = base.extractor
            if delta.structural:
                with span("delta.extract", self._m_delta["extract"]) as sp:
                    extractor = SubgraphExtractor(new_g, self.cfg.out_cap,
                                                  self.cfg.in_cap)
                spans.append(("extract", sp))
            touched = delta.touched_nodes()
            # the spill's generation bumps in the swap's critical section,
            # which holds the spill IO lock (lock order: spill IO, then
            # service): no write is in flight across it (_drain_spill takes
            # its list under that lock), pending writes of touched vectors
            # are dropped below, and a disk read from before the bump is
            # not admitted at the new version (pipeline.assemble)
            io = self._spill_io_lock if self._spill is not None \
                else nullcontext()
            gen = None
            with io, self._lock, \
                    span("delta.swap", self._m_delta["swap"]) as sp_swap:
                version = base.version + 1
                self._live = GraphVersion(version, new_g, extractor, table)
                if delta.pages:
                    self._warm_h = np.concatenate(
                        [self._warm_h, np.zeros(delta.pages)])
                    self._warm_seen = np.concatenate(
                        [self._warm_seen, np.zeros(delta.pages, bool)])
                doomed = {k for k, e in self._cache.items()
                          if np.isin(e.nodes, touched,
                                     assume_unique=True).any()}
                for k in doomed:
                    del self._cache[k]
                # pending writes of now-stale vectors must not reach disk:
                # evictees (policy "evict") are no longer in the cache, so
                # filter by the touched pages, not by the doomed keys
                self._spill_pending = [
                    p for p in self._spill_pending
                    if not np.isin(p[1], touched, assume_unique=True).any()]
                survivors = [(k, e.nodes, e.authority, e.hub)
                             for k, e in self._cache.items()]
                if self._spill is not None:
                    gen = self._spill.bump_data_generation()
                self._m_delta_invalidated.inc(len(doomed))
                self._m_links_added.inc(len(np.unique(
                    delta.adds[:, 0] * new_g.n_nodes + delta.adds[:, 1])))
                self._m_links_removed.inc(len(np.unique(
                    delta.removes[:, 0] * new_g.n_nodes
                    + delta.removes[:, 1])))
                self._m_pages_added.inc(delta.pages)
                self._m_version.set(version)
            spans.append(("swap", sp_swap))
            if gen is not None and self.cfg.spill_policy == "all" \
                    and survivors:
                # everything on disk just went stale; re-spill the still-
                # valid entries under the new generation so a restart
                # keeps them (only pre-delta state for touched subgraphs
                # must die)
                with self._lock:
                    self._spill_pending.extend(survivors)
                self._drain_spill()
        spans.append(("roll", sp_roll))
        self.delta_trace.extend((version, stage, sp.t0, sp.t1)
                                for stage, sp in spans)
        return {"version": version, "structural": delta.structural,
                "pages": delta.pages, "invalidated": len(doomed),
                "touched_nodes": int(len(touched)),
                "data_generation": gen,
                "swap_ms": (sp_swap.t1 - sp_swap.t0) * 1e3,
                "roll_ms": (sp_roll.t1 - sp_roll.t0) * 1e3}

    def snapshot_stats(self) -> dict:
        """A consistent copy of the stats counters (the legacy key set).

        The live ``stats`` view is mutated under the service lock by
        pipeline stages running on the prepare worker and the driving
        thread; client threads (e.g. monitoring loops over a busy
        ``RankQueue``) should read through this accessor instead of
        iterating the live view mid-update. The full typed registry
        renders through ``telemetry_snapshot()`` instead.
        """
        with self._lock:
            out = dict(self.stats)
            out["backend_batches"] = dict(self.stats["backend_batches"])
            return out

    def telemetry_snapshot(self) -> dict:
        """The full registry rendering (counters/gauges as scalars,
        histograms as count/sum/min/max/p50/p95/p99) — what the
        ``/stats.json`` endpoint serves for this service. Level gauges
        (cache sizes) are sampled here, at render time."""
        with self._lock:
            self.telemetry.gauge("service.cache.entries").set(
                len(self._cache))
            self.telemetry.gauge("service.plan_cache.entries").set(
                len(self._plans))
        return self.telemetry.snapshot()

    # -- serving ----------------------------------------------------------

    def validate_roots(self, roots: Sequence[int]) -> np.ndarray:
        """Deduped, sorted, range-checked root set (the canonical form every
        entry point — sync ``rank`` and the async queue — validates to).

        The range check runs on the int64 ids BEFORE the int32 downcast:
        downcasting first would wrap ids >= 2^31 (2**32 becomes node 0)
        and silently validate garbage as a real page. Likewise the int64
        cast itself must not invent ids: a float 3.7 would truncate to
        node 3 and serve the wrong page, and strings/bools/complex are
        never page ids — only integers and integral floats pass.
        """
        arr = np.asarray(roots)
        if arr.dtype.kind == "f":
            if not np.all(np.isfinite(arr)) or \
                    not np.array_equal(arr, np.trunc(arr)):
                raise ValueError(
                    f"root ids must be integral, got float values "
                    f"{np.asarray(arr).ravel()[:8]}")
        elif arr.dtype.kind not in "iu":
            raise ValueError(
                f"root ids must be integers, got dtype {arr.dtype}")
        roots_u = np.unique(arr.astype(np.int64))
        if len(roots_u) == 0:
            raise ValueError("empty root set")
        n = self.g.n_nodes  # the live version's: a later one only grows
        if roots_u[0] < 0 or roots_u[-1] >= n:
            # negative ids would silently wrap through numpy indexing
            raise ValueError(
                f"root ids must be in [0, {n}); got "
                f"[{roots_u[0]}, {roots_u[-1]}]")
        return roots_u.astype(np.int32)

    def rank(self, queries: Sequence[Sequence[int]], *,
             refresh: bool = False) -> List[QueryResult]:
        """Rank a list of root sets. Chunks of ``v_max`` queries share one
        traversal; multi-chunk streams execute through the staged pipeline
        (``serve.pipeline``), overlapping each chunk's host assembly with
        the previous chunk's device sweep at ``pipeline_depth >= 2``.
        ``refresh`` re-iterates exact cache hits (warm-started) instead of
        serving the stored scores."""
        from .pipeline import PipelineJob

        # validate everything before serving anything: a mid-batch raise
        # would lose computed results and corrupt the stats counters
        clean = [self.validate_roots(roots) for roots in queries]
        v = self.cfg.v_max
        jobs = [PipelineJob(queries=clean[i:i + v], refresh=refresh)
                for i in range(0, len(clean), v)]
        out: List[QueryResult] = []
        gen = self.pipeline.run(jobs)
        try:
            for _job, results, exc in gen:
                if exc is not None:
                    raise exc
                out.extend(results)
        finally:
            gen.close()  # unwind the prepare worker if we raised mid-run
        return out

    def _start_vector(self, fs: FocusedSubgraph, entry, m: np.ndarray,
                      loc: np.ndarray):
        """Column start vector (union-local) + its status label.

        Exact-key refresh warm-starts from the cached hub vector; otherwise
        the global warm table supplies scores for previously-seen nodes if
        they cover enough of the base set. Fallback: the uniform vector
        over S_j (what ``accel_hits`` cold-starts from).
        """
        n_u = len(m)
        v = np.zeros(n_u)
        if entry is not None and len(entry.nodes) == len(fs.nodes) \
                and (entry.nodes == fs.nodes).all():
            v[loc] = entry.hub
            if v.sum() > 0:
                return v / np.abs(v).sum(), "warm"
        seen = self._warm_seen[fs.nodes]
        if seen.mean() >= self.cfg.warm_min_overlap:
            v[loc] = np.where(seen, self._warm_h[fs.nodes], 0.0)
            tot = np.abs(v).sum()
            if tot > 0:
                # unseen nodes get the mean warm mass so no page starts dead
                fill = tot / max(seen.sum(), 1)
                v[loc] = np.where(seen, v[loc], fill)
                return v / np.abs(v).sum(), "warm"
        v[:] = 0.0
        v[loc] = 1.0 / len(fs.nodes)
        return v, "cold"
