"""Serving benchmark: batched-V query ranking vs sequential per-query
``accel_hits``, warm vs cold starts, the sweep-backend axis, and the
arrival-rate axis (sync one-at-a-time vs the async micro-batching queue).

Acceptance targets (ISSUE 1): on a 10k-node synthetic webgraph the batched
service sustains >= 3x the sequential per-query throughput, and batched
scores match the per-query oracle to <= 1e-8 L1. ISSUE 2 adds the backend
axis: every backend must hold the same oracle match, and ``--backend
sharded`` additionally measures the dist.py collective ladder (dual_blocked
must move no more wire bytes per sweep than replicated). ISSUE 3 adds the
arrival axis: requests arriving at ``--rates`` q/s served one-at-a-time
(sync, virtual-clock single-server model over measured per-call times) vs
submitted through ``RankQueue`` (real dispatcher, real sleeps) — p50/p95
latency and throughput per rate, plus a queued==sync parity check. ISSUE 4
adds the plan-hit-rate axis: the same repeat stream served cold-plan vs
warm-plan (vector cache cleared between passes, ``SweepPlan`` cache kept)
per backend — the warm leg must hit the plan cache every batch, and on the
layout-heavy backends (sharded, bsr) must be measurably faster. ISSUE 5
adds the overlap axis: the same multi-batch stream dispatched serially
(pipeline depth 1) vs pipelined (depth 2 — host assemble/plan of batch
k+1 overlaps batch k's device sweep), as a sync stream and a queued
burst; pipelined must match serial <=1e-10 L1 (armed in --smoke) and beat
it on q/s in full runs. ISSUE 6 adds the rank-stability axis (residual
vs top-k-stable stopping on Peserico-Pretto slow-rank gadgets — the
early-exit leg must cut mean sweeps >= 2x at identical top-k) and the
overload axis (the same mixed-priority storm through a shed-nothing
"collapse" queue vs the SLA queue — shedding plus early exit must hold
the high-priority p95 where collapse lets it balloon). ISSUE 7 adds the
precision axis: bf16/fp32 bulk sweeps with certified f64 refinement must
match the single-phase f64 service <= 1e-10 L1 with every residual
certificate <= the polish tol (armed in --smoke), while the per-sweep cost
at the bulk dtype beats f64 >= 2x (full runs only) — plus a served-only
percentile check on the overload axis (shedding must never *lower* a
class's reported p95). ISSUE 10 adds the lumping axis: duplicate-heavy
and dangling-heavy graphs served ``lumping=off`` vs ``on`` — the
plan-time reduction must not change the math (<= 1e-10 L1, armed in
--smoke) while actually shrinking the swept matrix (lumped rows >= 1,
armed in --smoke) and improving per-sweep time (full runs only).

``--smoke`` shrinks everything to a seconds-scale CI tripwire (tiny graph,
few queries, perf gates skipped — correctness gates still enforced).

  PYTHONPATH=src python -m benchmarks.serve_rank_bench
  PYTHONPATH=src python benchmarks/serve_rank_bench.py --backend bsr
  PYTHONPATH=src python benchmarks/serve_rank_bench.py --smoke
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python benchmarks/serve_rank_bench.py --backend sharded
"""
from __future__ import annotations

import argparse
import time

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

from repro.core import accel_hits  # noqa: E402
from repro.graph import Graph, WebGraphSpec, generate_webgraph  # noqa: E402
from repro.launch.serve_rank import roll_delta  # noqa: E402
from repro.serve import RankService, RankServiceConfig  # noqa: E402


def measure_collective_ladder(svc, queries, v, n_devices=None, dtype_bytes=8):
    """Compile one sweep per shard mode at this workload's padded shapes
    and measure per-device wire bytes from the optimized HLO (the dist.py
    ladder, measured rather than asserted)."""
    from repro.graph.structure import next_pow2
    from repro.serve.backends import ShardedSweepBackend

    union = svc.extractor.extract_union(
        [svc.extractor.extract(q) for q in queries[:v]])
    n_pad = next_pow2(max(union.n_nodes + 1, 16))
    src, dst = union.graph.src, union.graph.dst
    w = np.ones(union.graph.n_edges)
    out = {}
    for mode in ("replicated", "dual_blocked"):
        be = ShardedSweepBackend(mode=mode, n_devices=n_devices)
        out[mode] = {"measured": be.measure_wire_bytes(n_pad, v, src, dst, w),
                     "analytic": be.collective_bytes_per_sweep(
                         n_pad, v, dtype_bytes)}
    return n_pad, out


def plan_axis(g, cfg, queries, backends):
    """Cold-plan vs warm-plan per-batch latency per backend (ISSUE 4).

    The same stream is served twice by ONE service: between passes the
    converged-vector state is cleared (``clear_result_cache``) but cached
    ``SweepPlan``s are kept, so both passes run identical device sweeps
    (same cold starts, same iteration counts) and differ only in host-side
    layout work — edge shards, BSR blocking/permutation, device edge
    transfer. The repeat-traffic leg must hit the plan cache on every
    batch; the latency delta is the plan cache's whole value proposition.

    Returns [(backend, us/batch cold, us/batch warm, hits, misses)].
    """
    rows = []
    for kind in backends:
        RankService(g, cfg(backend=kind)).rank(queries)  # compile warmup
        svc = RankService(g, cfg(backend=kind))
        t0 = time.perf_counter()
        svc.rank(queries)
        t_cold = time.perf_counter() - t0
        n_batches = svc.stats["batches"]
        hits_cold = svc.stats["plan_hits"]
        svc.clear_result_cache()  # cold vectors, warm plans
        t0 = time.perf_counter()
        svc.rank(queries)
        t_warm = time.perf_counter() - t0
        hits = svc.stats["plan_hits"] - hits_cold
        rows.append((kind, t_cold / n_batches * 1e6,
                     t_warm / n_batches * 1e6, hits,
                     svc.stats["plan_misses"]))
    return rows


def pipeline_axis(g, cfg, queries, deadline_ms):
    """Serial (depth-1) vs pipelined (depth-2) dispatch on the same
    multi-batch stream (ISSUE 5's overlap axis).

    Two legs per depth: the synchronous multi-batch ``rank()`` stream and
    a queued burst (real dispatcher, back-to-back submissions — the
    arrival leg where overlap matters most). Fresh cold services per
    depth, compile caches pre-warmed, so the delta is dispatch schedule
    only: at depth 2 batch k+1's host assemble/plan (and the queue's
    flush wait) runs while batch k sweeps on device. Solves at tol<=1e-12
    (like the arrival axis) so the <=1e-10 parity gate has headroom —
    the two schedules reach the same fixed points from slightly different
    warm-start states.

    Returns ([(depth, sync us/batch, sync q/s, queued q/s, overlaps)],
    parity_l1 between the depth-1 and depth-2 sync results).
    """
    tight = {"tol": min(1e-12, cfg().tol)}
    base = cfg
    cfg = lambda **kw: base(**{**tight, **kw})  # noqa: E731

    RankService(g, cfg()).rank(queries)  # compile warmup (all buckets)
    rows, res = [], {}
    for depth in (1, 2):
        svc = RankService(g, cfg(pipeline_depth=depth))
        t0 = time.perf_counter()
        res[depth] = svc.rank(queries)
        dt = time.perf_counter() - t0
        n_batches = max(svc.stats["batches"], 1)
        overlaps = svc.pipeline.overlap_events()

        svcq = RankService(g, cfg(pipeline_depth=depth))
        t0 = time.perf_counter()
        with svcq.queue(deadline_ms=deadline_ms) as rq:
            tickets = [rq.submit(q) for q in queries]
            for t in tickets:
                t.result(timeout=600)
        q_qps = len(queries) / (time.perf_counter() - t0)
        rows.append((depth, dt / n_batches * 1e6, len(queries) / dt,
                     q_qps, overlaps))
    parity_l1 = max(float(np.abs(a.authority - b.authority).sum())
                    for a, b in zip(res[1], res[2]))
    return rows, parity_l1


def arrival_axis(g, cfg, queries, rates, deadline_ms):
    """Latency/throughput at each arrival rate: sync one-at-a-time (a
    virtual-clock single-server queue over measured per-call times) vs the
    async micro-batching ``RankQueue`` (real dispatcher, real sleeps).

    Returns [(rate, sync row, queued row)] plus the max L1 between queued
    results and a fresh synchronous service on the same stream (the
    frontend must not change the math). Solves at tol<=1e-12 so the parity
    bound has headroom over the residual floor: queue flush patterns group
    queries differently than v_max chunking, and two fixed points reached
    from different warm starts agree only to O(tol)."""
    import numpy as np

    tight = {"tol": min(1e-12, cfg().tol)}
    base = cfg
    cfg = lambda **kw: base(**{**tight, **kw})  # noqa: E731

    # measured per-request service times, one at a time (v=1, pre-warmed)
    RankService(g, cfg(v_max=1)).rank(queries)  # compile warmup
    svc1 = RankService(g, cfg(v_max=1))
    dur = []
    for q in queries:
        t0 = time.perf_counter()
        svc1.rank([q])
        dur.append(time.perf_counter() - t0)

    sync_ref = RankService(g, cfg()).rank(queries)  # parity oracle
    # deadline flushes dispatch narrow batches whose union subgraphs land in
    # smaller n_pad buckets than the v_max chunks above — compile those now
    # so no timed run pays a trace
    wsvc = RankService(g, cfg())
    for q in queries:
        wsvc.rank([q])
    rows, parity_l1 = [], 0.0
    for rate in rates:
        gap = 1.0 / rate if rate > 0 else 0.0
        # sync model: requests queue behind the single blocking server
        t_free, lat_s = 0.0, []
        for i, d in enumerate(dur):
            arr = i * gap
            start = max(arr, t_free)
            t_free = start + d
            lat_s.append(t_free - arr)
        sync = {"qps": len(dur) / t_free, "lat": np.array(lat_s) * 1e3}

        # queued: the real thing, fresh service per rate (cold cache)
        svcq = RankService(g, cfg())
        t0 = time.perf_counter()
        with svcq.queue(deadline_ms=deadline_ms) as rq:
            tickets = []
            for i, q in enumerate(queries):
                target = t0 + i * gap
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
                tickets.append(rq.submit(q))
            res = [t.result(timeout=600) for t in tickets]
        span = time.perf_counter() - t0
        queued = {"qps": len(queries) / span,
                  "lat": np.array([t.latency_s for t in tickets]) * 1e3,
                  "batches": rq.stats["batches"],
                  "vmax": rq.stats["flush_vmax"],
                  "deadline": rq.stats["flush_deadline"]}
        parity_l1 = max(parity_l1, max(
            float(np.abs(a.authority - b.authority).sum())
            for a, b in zip(sync_ref, res)))
        rows.append((rate, sync, queued))
    return rows, parity_l1


def slow_rank_gadgets(n_gadgets, big=12):
    """Peserico & Pretto's slow-rank regime as a serving workload.

    Each gadget is two node-disjoint complete digraphs K_big and
    K_{big-1}: the secondary/principal eigenvalue ratio is
    ((big-2)/(big-1))**2, so the *scores* converge slowly (~145 sweeps at
    tol 1e-12 for big=12) while the *ranking* — every K_big node above
    every K_{big-1} node, ties broken by index — locks after one sweep.
    Gadgets are disjoint and each query roots into its own gadget, so no
    cache hit or warm-start crossover clouds the iteration counts.

    Returns (graph, [roots per gadget]).
    """
    from repro.graph.structure import Graph

    per = 2 * big - 1
    src, dst, queries = [], [], []
    for gi in range(n_gadgets):
        base = gi * per
        for size, off in ((big, 0), (big - 1, big)):
            i = np.arange(size)
            s, d = np.repeat(i, size), np.tile(i, size)
            keep = s != d
            src.append(base + off + s[keep])
            dst.append(base + off + d[keep])
        queries.append(np.array([base, base + big]))
    g = Graph(n_gadgets * per, np.concatenate(src), np.concatenate(dst))
    return g, queries


def _gadget_cfg(rank_k, **kw):
    # caps wide enough to pull a whole 23-node gadget into the base set;
    # dense backend: the admission/stopping axes are backend-agnostic
    # (cross-backend stopping parity is pinned by tests, not re-timed here)
    kw.setdefault("v_max", 4)
    kw.setdefault("tol", 1e-12)
    kw.setdefault("backend", "dense")
    return RankServiceConfig(out_cap=64, in_cap=64, rank_k=rank_k, **kw)


def early_exit_axis(rank_k, stable_sweeps=2, n_gadgets=8):
    """Residual-only vs rank-stability stopping on the slow-rank gadgets
    (ISSUE 6 tentpole acceptance): same queries, same backend; the rank_k
    leg must cut mean sweeps >= 2x and return the identical top-k.

    Returns (mean sweeps exact, mean sweeps early-exit, topk identical).
    """
    g, queries = slow_rank_gadgets(n_gadgets)
    res = {}
    for k in (0, rank_k):
        cfg = _gadget_cfg(k, stable_sweeps=stable_sweeps)
        RankService(g, cfg).rank(queries)  # compile warmup
        res[k] = RankService(g, cfg).rank(queries)
    it_exact = float(np.mean([r.iters for r in res[0]]))
    it_rank = float(np.mean([r.iters for r in res[rank_k]]))
    topk_same = all(
        [n for n, _ in a.topk(rank_k)] == [n for n, _ in b.topk(rank_k)]
        for a, b in zip(res[0], res[rank_k]))
    return it_exact, it_rank, topk_same


def overload_axis(rank_k, deadline_ms, n_gadgets=24, max_pending=8):
    """SLA admission under overload: one back-to-back storm (every 3rd
    request high priority, the rest best-effort), served twice.

    The *collapse* leg is the pre-SLA queue — nothing sheddable
    (shed_priority above every class), exact-residual stopping — so every
    request backpressure-blocks behind full slow-rank batches and the
    high-priority p95 collapses with the rest. The *sla* leg sheds
    best-effort traffic at admission, degrades rank_k under backlog, and
    early-exits rank-stable columns; its high-priority p95 must beat the
    collapse leg's while every shed ticket resolves during the storm.

    Returns {leg: {p95_hi_ms, qps, stats, shed_prompt}}.
    """
    g, queries = slow_rank_gadgets(n_gadgets)
    prios = [0 if i % 3 == 0 else 1 for i in range(len(queries))]
    out = {}
    for leg, k, shed_pri in (("collapse", 0, 10 ** 9), ("sla", rank_k, 1)):
        # warm every shape the storm can dispatch: union n_pad/e_pad
        # buckets for batch widths 1..v_max (disjoint query slices — a
        # repeated slice is a cache hit and sweeps nothing, leaving the
        # multi-gadget shapes uncompiled), plus the degraded-rank_k
        # recompile the SLA leg triggers under backlog (rank_k is a
        # static jit arg)
        for warm_k in ({k, max(1, k // 2)} if k else {0}):
            w = RankService(g, _gadget_cfg(warm_k))
            i0 = 0
            for width in range(1, w.cfg.v_max + 1):
                w.rank(queries[i0:i0 + width])
                i0 += width
        svc = RankService(g, _gadget_cfg(k, shed_priority=shed_pri))
        t0 = time.perf_counter()
        with svc.queue(deadline_ms=deadline_ms,
                       max_pending=max_pending) as rq:
            tickets = [rq.submit(q, priority=p, deadline_ms=deadline_ms)
                       for q, p in zip(queries, prios)]
            # shed tickets must resolve *at admission* — snapshot before
            # blocking on the served ones
            done_at_storm_end = [t.done() for t in tickets]
            results = [t.result(timeout=600) for t in tickets]
        span = time.perf_counter() - t0
        shed_prompt = all(done for r, done in zip(results, done_at_storm_end)
                          if r.status == "shed")
        hi = [t.latency_s * 1e3 for t, p in zip(tickets, prios) if p == 0]
        # bench-side served-only latencies for the sheddable class: the
        # queue's reported percentiles must match these, never the (lower)
        # shed-diluted mix — shedding must not flatter a class's p95
        lo_served = [t.latency_s * 1e3
                     for t, p, r in zip(tickets, prios, results)
                     if p == 1 and r.status != "shed"]
        out[leg] = {"p95_hi_ms": float(np.percentile(hi, 95)),
                    "p95_lo_served_ms": (float(np.percentile(lo_served, 95))
                                         if lo_served else None),
                    "qps": len(queries) / span,
                    "stats": rq.snapshot_stats(),
                    "shed_prompt": shed_prompt}
    return out


def stats_endpoint_axis(g, cfg, queries, deadline_ms):
    """Ops-endpoint leg (ISSUE 8): a ``StatsServer`` composed over a live
    service + queue — the launcher's ``--stats-port`` wiring — is probed
    over HTTP *during* a queued burst. ``/healthz`` must answer 200 ok
    and ``/stats.json`` must parse mid-flight and, after the burst,
    carry registry counts consistent with the traffic served.

    Returns (healthz_ok, stats_ok, final snapshot).
    """
    import json
    import urllib.request

    from repro.serve import StatsServer

    svc = RankService(g, cfg())
    with svc.queue(deadline_ms=deadline_ms) as rq:
        srv = StatsServer(lambda: {"service": svc.telemetry_snapshot(),
                                   "queue": rq.telemetry_snapshot()},
                          port=0)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            tickets = [rq.submit(q) for q in queries]
            # probe while tickets are in flight — the endpoint must render
            # a consistent snapshot off live, mutating registries
            with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
                healthz_ok = r.status == 200 and r.read() == b"ok"
            with urllib.request.urlopen(base + "/stats.json",
                                        timeout=30) as r:
                live = json.loads(r.read())
            for t in tickets:
                t.result(timeout=600)
            with urllib.request.urlopen(base + "/stats.json",
                                        timeout=30) as r:
                snap = json.loads(r.read())
        finally:
            srv.close()
    stats_ok = (
        "queue.submitted" in live["queue"]
        and snap["queue"]["queue.submitted"] == len(queries)
        and snap["service"]["service.batches"]
        == snap["queue"]["queue.batches"] >= 1
        and snap["service"]["pipeline.stage_ms"]["sweep"]["count"] >= 1)
    return healthz_ok, stats_ok, snap


def delta_swap_axis(g, cfg, queries, deadline_ms):
    """Zero-downtime edge-delta roll (ISSUE 9; armed in --smoke).

    Live guaranteed traffic through the queue, then the operator roll
    with admission open (``launch.serve_rank.roll_delta``: a reweight
    inside query 0's union, so the delta provably changes what that
    query serves), then the whole stream resubmitted. Gates: zero
    guaranteed-class sheds across
    the roll, at least one plan *patched* in place with
    ``service.plan.misses`` unmoved (weight-only deltas must not rebuild
    surviving layouts), and every post-delta result <= 1e-10 L1 of a
    cold-built oracle service that never saw the pre-delta graph.
    """
    svc = RankService(g, cfg())
    with svc.queue(deadline_ms=deadline_ms) as rq:
        pre = [rq.submit(q) for q in queries]  # all guaranteed class
        for t in pre:
            t.result(timeout=600)
        fs = svc.extractor.extract(queries[0])
        u = int(fs.nodes[fs.graph.src[0]])
        v = int(fs.nodes[fs.graph.dst[0]])
        misses_before = svc.stats["plan_misses"]
        summ = roll_delta(svc, {"reweights": [(u, v, 2.0)]})
        roll_ms = summ["roll_ms"]
        post = [t.result(timeout=600)
                for t in [rq.submit(q) for q in queries]]
        stats = rq.snapshot_stats()
    patched = sum(svc.telemetry_snapshot()["service.delta.patched"].values())
    built = svc.stats["plan_misses"] - misses_before

    oracle = RankService(g, cfg())
    oracle.apply_edge_delta(reweights=[(u, v, 2.0)])
    l1 = max(float(np.abs(a.authority - b.authority).sum())
             for a, b in zip(post, oracle.rank(queries)))
    shed0 = stats["classes"].get(0, {}).get("shed", -1)
    return {"l1": l1, "patched": patched, "built": built,
            "invalidated": summ["invalidated"], "swap_ms": summ["swap_ms"],
            "roll_ms": roll_ms, "shed0": shed0,
            "served0": stats["classes"].get(0, {}).get("served", 0)}


def _clone_heavy_graph(n_hubs, clones, seed=0):
    """Hubs over a random backbone, each fanning out to ``clones`` sink
    nodes with identical in-adjacency: one duplicate class per hub."""
    rng = np.random.default_rng(seed)
    src, dst = [], []
    for i in range(n_hubs):
        for j in range(n_hubs):
            if i != j and rng.random() < 0.5:
                src.append(i)
                dst.append(j)
    n = n_hubs
    for h in range(n_hubs):
        src.extend([h] * clones)
        dst.extend(range(n, n + clones))
        n += clones
    g = Graph(n, np.asarray(src, np.int32), np.asarray(dst, np.int32))
    return g, list(range(n_hubs))


def _dangling_heavy_graph(core, isolated, seed=1):
    """A connected core plus fully isolated satellites: queries rooted on
    satellites pull zero-degree rows into their unions."""
    g0 = generate_webgraph(WebGraphSpec(core, core * 6, 0.3, seed=seed))
    g = Graph(core + isolated, g0.src, g0.dst)
    return g, list(range(core, core + isolated))


def lumping_axis(v, tol, smoke):
    """Plan-time lumped sweep reduction (ISSUE 10; parity armed in --smoke).

    Two reducible graph families, each served lumping="off" vs "on" on
    the same stream: duplicate-heavy (hub fans to clone sinks — whole
    classes collapse to one multiplicity-weighted representative) and
    dangling-heavy (isolated roots drag zero-degree rows into the union
    — they drop entirely). Gates: <= 1e-10 L1 parity and a real row
    reduction (lumped rows >= 1, i.e. reduced rows < full rows) armed in
    --smoke; per-sweep time improvement on the duplicate-heavy leg in
    full runs (the reduction must cross pow2 shape buckets to pay).
    """
    hubs, clones = (4, 24) if smoke else (12, 96)
    fams = {
        "duplicate_heavy": _clone_heavy_graph(hubs, clones),
        "dangling_heavy": _dangling_heavy_graph(
            40 if smoke else 200, 80 if smoke else 400),
    }
    out = {}
    for fam, (g2, roots) in fams.items():
        rng = np.random.default_rng(3)
        qs = [rng.choice(roots, size=min(3, len(roots)), replace=False)
              for _ in range(4 if smoke else 12)]

        def c(lumping):
            return RankServiceConfig(v_max=v, tol=tol, lumping=lumping,
                                     out_cap=2 * clones, in_cap=64)

        def run(lumping):
            RankService(g2, c(lumping)).rank(qs)  # compile warmup
            svc = RankService(g2, c(lumping))
            res = svc.rank(qs)
            sweep_s = sum(t1 - t0 for _r, _j, st, t0, t1
                          in svc.pipeline.trace if st == "sweep")
            us = sweep_s / max(svc.stats["sweeps"], 1) * 1e6
            return res, us, svc.telemetry_snapshot()

        off, us_off, _ = run("off")
        on, us_on, snap = run("on")
        l1 = max(max(float(np.abs(a.authority - b.authority).sum()),
                     float(np.abs(a.hub - b.hub).sum()))
                 for a, b in zip(off, on))
        ratio = snap["service.plan.reduction_ratio"]
        out[fam] = {"l1": l1, "us_off": us_off, "us_on": us_on,
                    "lumped": snap["service.plan.lumped_nodes"],
                    "ratio_max": ratio["max"] or 0.0}
    return out


def precision_axis(g, cfg, queries, smoke):
    """Mixed-precision sweeps with certified f64 refinement (ISSUE 7).

    Correctness leg (armed in --smoke): bf16- and fp32-bulk ladder
    services on the same stream as the single-phase f64 service — fixed
    points must agree <= 1e-10 L1 and every cold result must carry a
    residual certificate <= the polish tolerance. Solves at tol <= 1e-12
    (like the other parity axes) so the 1e-10 gate has headroom.

    Throughput leg (full runs only): per-sweep seconds of a pure-f32
    service vs a pure-f64 service at a loose tol — the bulk phase's cost
    model, isolated from polish and convergence-count effects (sweep-stage
    wall time from the pipeline trace over the service's sweep counter).
    The segment-sum traversal is memory-bound, so halving the bytes must
    roughly halve the per-sweep time (>= 2x gate).

    Returns (parity_l1, cert_max, cert_tol, per_sweep_us by dtype | None,
    f64/f32 per-sweep speedup | None).
    """
    tight = {"tol": min(1e-12, cfg().tol)}
    base = cfg
    cfg = lambda **kw: base(**{**tight, **kw})  # noqa: E731

    RankService(g, cfg()).rank(queries)  # compile warmup
    ref = RankService(g, cfg()).rank(queries)
    parity_l1, cert_max, cert_tol = 0.0, 0.0, None
    for sd in ("float32", "bfloat16"):
        RankService(g, cfg(sweep_dtype=sd)).rank(queries)  # ladder warmup
        svc = RankService(g, cfg(sweep_dtype=sd))
        res = svc.rank(queries)
        parity_l1 = max(parity_l1, max(
            float(np.abs(a.authority - b.authority).sum())
            for a, b in zip(ref, res)))
        certs = [r.residual for r in res]
        assert all(c is not None for c in certs), sd
        cert_max = max(cert_max, max(certs))
        cert_tol = svc._polish_tol

    per_sweep, speed = None, None
    if not smoke:
        per_sweep = {}
        for dt in (np.float64, np.float32):
            # pure-dtype services at a loose tol both dtypes can resolve:
            # the measured quantity is seconds per sweep, normalized by
            # each service's own sweep counter (iteration counts need not
            # match across dtypes)
            RankService(g, base(dtype=dt, tol=2e-4)).rank(queries)  # warm
            svc = RankService(g, base(dtype=dt, tol=2e-4))
            svc.rank(queries)
            sweep_s = sum(t1 - t0 for _r, _j, st, t0, t1
                          in svc.pipeline.trace if st == "sweep")
            per_sweep[np.dtype(dt).name] = \
                sweep_s / max(svc.stats["sweeps"], 1) * 1e6
        speed = per_sweep["float64"] / max(per_sweep["float32"], 1e-12)
    return parity_l1, cert_max, cert_tol, per_sweep, speed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-nodes", type=int, default=10000)
    ap.add_argument("--n-edges", type=int, default=80000)
    ap.add_argument("--dangling", type=float, default=0.6)
    ap.add_argument("--n-queries", type=int, default=48)
    ap.add_argument("--roots", type=int, default=5)
    ap.add_argument("--v", type=int, default=8)
    ap.add_argument("--tol", type=float, default=1e-10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="dense",
                    choices=["dense", "sharded", "bsr", "auto"])
    ap.add_argument("--shard-mode", default="dual_blocked",
                    choices=["replicated", "dual_blocked"])
    ap.add_argument("--shard-devices", type=int, default=None)
    ap.add_argument("--rates", default="0,100",
                    help="comma-separated arrival rates (q/s; 0 = "
                         "back-to-back) for the sync-vs-queued axis")
    ap.add_argument("--deadline-ms", type=float, default=5.0,
                    help="queue flush deadline for the arrival axis")
    ap.add_argument("--rank-k", type=int, default=4,
                    help="top-k width for the rank-stability early-exit "
                         "and overload axes")
    ap.add_argument("--gadgets", type=int, default=24,
                    help="slow-rank gadget count for the overload axis")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI tripwire: tiny graph, few "
                         "queries, perf gates skipped")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        args.n_nodes = min(args.n_nodes, 400)
        args.n_edges = min(args.n_edges, 3200)
        args.n_queries = min(args.n_queries, 8)
        args.v = min(args.v, 4)
        args.rates = "0,100"

    g = generate_webgraph(WebGraphSpec(args.n_nodes, args.n_edges,
                                       args.dangling, seed=args.seed))
    print(f"graph: N={g.n_nodes} E={g.n_edges} "
          f"dangling={g.dangling_fraction():.1%}")
    rng = np.random.default_rng(args.seed)
    queries = [rng.choice(g.n_nodes, size=args.roots, replace=False)
               for _ in range(args.n_queries)]

    def cfg(**kw):
        kw.setdefault("v_max", args.v)
        kw.setdefault("tol", args.tol)
        kw.setdefault("backend", args.backend)
        return RankServiceConfig(shard_mode=args.shard_mode,
                                 shard_devices=args.shard_devices, **kw)

    svc = RankService(g, cfg())

    # --- sequential per-query oracle (accel_hits on each focused subgraph).
    # NB: this is the real cost of serving queries one at a time through the
    # oracle API — power_method re-jits its sweep per call, so every query
    # pays a retrace+compile. The v1-service line below isolates the
    # batching win with compilation excluded on BOTH sides.
    subs = [svc.extractor.extract(q) for q in queries]
    t0 = time.perf_counter()
    oracle = [accel_hits(fs.graph, tol=args.tol) for fs in subs]
    t_seq = time.perf_counter() - t0
    qps_seq = args.n_queries / t_seq

    # --- batched-V cold service. A full warmup pass on a throwaway service
    # populates the module-level jit cache for every shape bucket, so the
    # timed run has zero compiles.
    warmup = RankService(g, cfg())
    warmup.rank(queries)
    t0 = time.perf_counter()
    batched = svc.rank(queries)
    t_bat = time.perf_counter() - t0
    qps_bat = args.n_queries / t_bat
    speedup = qps_bat / qps_seq

    # --- steady-state: same service machinery at V=1 vs V=args.v, both
    # pre-compiled (padded buckets), so the ratio is the batching win alone
    RankService(g, cfg(v_max=1)).rank(queries)
    svc1 = RankService(g, cfg(v_max=1))
    t0 = time.perf_counter()
    svc1.rank(queries)
    t_v1 = time.perf_counter() - t0
    qps_v1 = args.n_queries / t_v1
    speedup_steady = qps_bat / qps_v1

    # --- correctness: batched columns vs per-query oracle
    l1 = max(float(np.abs(np.asarray(o.aux) - r.authority).sum())
             for o, r in zip(oracle, batched))

    # --- warm vs cold restart (exact repeat, warm-started refresh)
    t0 = time.perf_counter()
    warm = svc.rank(queries, refresh=True)
    t_warm = time.perf_counter() - t0
    cold_iters = np.mean([r.iters for r in batched])
    warm_iters = np.mean([r.iters for r in warm])

    print("name,us_per_call,derived")
    print(f"serve/backend,0,kind={args.backend} "
          f"batches={svc.stats['backend_batches']}")
    print(f"serve/sequential_per_query,{t_seq / args.n_queries * 1e6:.1f},"
          f"qps={qps_seq:.1f}")
    print(f"serve/batched_v{args.v},{t_bat / args.n_queries * 1e6:.1f},"
          f"qps={qps_bat:.1f} speedup={speedup:.1f}x")
    print(f"serve/service_v1_steady,{t_v1 / args.n_queries * 1e6:.1f},"
          f"qps={qps_v1:.1f} batching_win={speedup_steady:.1f}x")
    print(f"serve/warm_refresh,{t_warm / args.n_queries * 1e6:.1f},"
          f"mean_iters warm={warm_iters:.1f} cold={cold_iters:.1f}")
    print(f"serve/oracle_match,0,max_l1={l1:.2e}")

    # --- arrival-rate axis: sync one-at-a-time vs async micro-batching
    rates = [float(r) for r in args.rates.split(",") if r != ""]
    rows, queue_l1 = arrival_axis(g, cfg, queries, rates, args.deadline_ms)
    for rate, sy, qu in rows:
        tag = f"{rate:g}qps" if rate > 0 else "burst"
        print(f"serve/arrival_{tag}_sync,"
              f"{np.mean(sy['lat']) * 1e3:.1f},"
              f"qps={sy['qps']:.1f} p50={np.percentile(sy['lat'], 50):.1f}ms"
              f" p95={np.percentile(sy['lat'], 95):.1f}ms")
        print(f"serve/arrival_{tag}_queued,"
              f"{np.mean(qu['lat']) * 1e3:.1f},"
              f"qps={qu['qps']:.1f} p50={np.percentile(qu['lat'], 50):.1f}ms"
              f" p95={np.percentile(qu['lat'], 95):.1f}ms "
              f"batches={qu['batches']} (vmax={qu['vmax']} "
              f"deadline={qu['deadline']})")

    # --- overlap axis: serial (depth-1) vs pipelined (depth-2) dispatch,
    # sync multi-batch stream + queued burst (ISSUE 5)
    pipe_rows, pipe_l1 = pipeline_axis(g, cfg, queries, args.deadline_ms)
    pipe_qps = {}
    for depth, us_b, s_qps, q_qps, overlaps in pipe_rows:
        pipe_qps[depth] = (s_qps, q_qps)
        print(f"serve/pipeline_depth{depth},{us_b:.1f},"
              f"sync_qps={s_qps:.1f} queued_qps={q_qps:.1f} "
              f"overlapped={overlaps}")

    # --- rank-stability axis: residual vs top-k-stable stopping on the
    # slow-rank gadgets (ISSUE 6; deterministic, armed in --smoke)
    it_exact, it_rank, topk_same = early_exit_axis(args.rank_k)
    print(f"serve/early_exit,0,mean_sweeps exact={it_exact:.1f} "
          f"rank_k{args.rank_k}={it_rank:.1f} "
          f"({it_exact / max(it_rank, 1e-9):.1f}x fewer)")

    # --- overload axis: the same mixed-priority storm through the
    # collapse queue vs the SLA queue (ISSUE 6; armed in --smoke)
    over = overload_axis(args.rank_k, args.deadline_ms, args.gadgets)
    for leg, row in over.items():
        s = row["stats"]
        print(f"serve/overload_{leg},0,p95_hi={row['p95_hi_ms']:.1f}ms "
              f"qps={row['qps']:.1f} shed={s['shed']} "
              f"(evicted {s['shed_evicted']}) degraded={s['degraded']} "
              f"deadline_miss={s['deadline_miss']}")

    # --- ops-endpoint axis: /healthz + /stats.json probed over HTTP
    # during a live queued burst (ISSUE 8; armed in --smoke)
    ok_health, ok_stats, ep_snap = stats_endpoint_axis(
        g, cfg, queries, args.deadline_ms)
    print(f"serve/stats_endpoint,0,"
          f"families={len(ep_snap['service']) + len(ep_snap['queue'])} "
          f"submitted={ep_snap['queue']['queue.submitted']} "
          f"batches={ep_snap['queue']['queue.batches']}")

    # --- delta-swap axis: a roll with admission open
    # under live guaranteed traffic (ISSUE 9; armed in --smoke)
    ds = delta_swap_axis(g, cfg, queries, args.deadline_ms)
    print(f"serve/delta_swap,0,patched={ds['patched']} built={ds['built']} "
          f"invalidated={ds['invalidated']} swap_ms={ds['swap_ms']:.1f} "
          f"roll_ms={ds['roll_ms']:.1f} class0_shed={ds['shed0']}")

    # --- lumping axis: plan-time reduced sweeps on duplicate-heavy and
    # dangling-heavy graphs (ISSUE 10; parity + reduction armed in --smoke)
    lump = lumping_axis(args.v, args.tol, args.smoke)
    for fam, row in lump.items():
        print(f"serve/lumping_{fam},{row['us_on']:.1f},"
              f"off_us_per_sweep={row['us_off']:.1f} "
              f"lumped_rows={row['lumped']} "
              f"max_reduction={row['ratio_max']:.0%} l1={row['l1']:.2e}")

    # --- precision axis: bf16/fp32 bulk sweeps + certified f64 refinement
    # (ISSUE 7; parity armed in --smoke, per-sweep speedup full runs only)
    prec_l1, cert_max, cert_tol, per_sweep, prec_speed = \
        precision_axis(g, cfg, queries, args.smoke)
    if per_sweep is not None:
        for name, us in per_sweep.items():
            print(f"serve/sweep_{name},{us:.1f},per-sweep (pure {name}, "
                  f"tol 2e-4)")

    # --- plan-hit-rate axis: cold-plan vs warm-plan latency per backend
    # (repeat traffic, cold vector cache — isolates the layout rebuild)
    plan_rows = plan_axis(g, cfg, queries, ("dense", "sharded", "bsr"))
    plan_hits_min, ok_plan_latency = None, True
    for kind, us_cold, us_warm, hits, misses in plan_rows:
        print(f"serve/plan_{kind},{us_warm:.1f},"
              f"cold_us_per_batch={us_cold:.1f} "
              f"speedup={us_cold / max(us_warm, 1e-9):.2f}x "
              f"plan_hits={hits} plan_misses={misses}")
        plan_hits_min = hits if plan_hits_min is None \
            else min(plan_hits_min, hits)
        if not args.smoke and kind in ("sharded", "bsr"):
            # ISSUE 4 acceptance: warm-plan serving must be measurably
            # faster than cold-plan on the layout-heavy backends
            ok_plan_latency = ok_plan_latency and us_warm < us_cold

    from repro.kernels import resolve_interpret
    # the >=3x gate targets compiled sweeps; BSR under the Pallas
    # interpreter (non-TPU hosts) is a correctness vehicle, not a perf one;
    # --smoke shrinks the workload below where perf ratios mean anything
    speed_gated = not args.smoke and not (args.backend == "bsr"
                                          and resolve_interpret(None))
    ok_speed = speedup >= 3.0 or not speed_gated
    ok_queue = queue_l1 <= 1e-10
    ok_match = l1 <= 1e-8
    ok_warm = warm_iters <= cold_iters
    ok_ladder = True
    if args.backend == "sharded":
        # the dist.py ladder, measured from compiled HLO at this workload's
        # padded shapes: dual_blocked must move no more bytes than replicated
        n_pad, ladder = measure_collective_ladder(svc, queries, args.v,
                                                  args.shard_devices)
        for mode, b in ladder.items():
            print(f"serve/collective_{mode},0,n_pad={n_pad} "
                  f"wire_bytes={b['measured']:.0f} "
                  f"analytic={b['analytic']}")
        ok_ladder = (ladder["dual_blocked"]["measured"]
                     <= ladder["replicated"]["measured"])
        print(f"ACCEPTANCE dual<=repl: {'PASS' if ok_ladder else 'FAIL'} "
              f"({ladder['dual_blocked']['measured']:.0f} vs "
              f"{ladder['replicated']['measured']:.0f} bytes)")
    skip_why = "smoke" if args.smoke else "bsr interpreter mode"
    print(f"ACCEPTANCE speedup>=3x: "
          f"{('PASS' if speedup >= 3.0 else 'FAIL') if speed_gated else f'SKIP ({skip_why})'} "
          f"({speedup:.1f}x)")
    print(f"ACCEPTANCE l1<=1e-8:   {'PASS' if ok_match else 'FAIL'} "
          f"({l1:.2e})")
    print(f"ACCEPTANCE warm<=cold: {'PASS' if ok_warm else 'FAIL'} "
          f"({warm_iters:.1f} vs {cold_iters:.1f})")
    print(f"ACCEPTANCE queued==sync<=1e-10: {'PASS' if ok_queue else 'FAIL'} "
          f"({queue_l1:.2e})")
    # the repeat-traffic leg must hit the plan cache on every backend —
    # armed in --smoke too (the CI tripwire the plan layer is gated by)
    ok_plan_hits = plan_hits_min is not None and plan_hits_min >= 1
    print(f"ACCEPTANCE plan_hits>=1: {'PASS' if ok_plan_hits else 'FAIL'} "
          f"(min over backends: {plan_hits_min})")
    print(f"ACCEPTANCE warm_plan<cold_plan: "
          f"{('PASS' if ok_plan_latency else 'FAIL') if not args.smoke else 'SKIP (smoke)'} "
          f"(sharded+bsr)")
    # ISSUE 5: pipelined dispatch must not change the math (armed in
    # --smoke) and must beat serial q/s on the multi-batch leg (full run;
    # best of sync-stream/queued-burst — tiny smoke graphs sweep too fast
    # to hide host work behind)
    ok_pipe_parity = pipe_l1 <= 1e-10
    print(f"ACCEPTANCE pipelined==serial<=1e-10: "
          f"{'PASS' if ok_pipe_parity else 'FAIL'} ({pipe_l1:.2e})")
    ok_pipe_speed = True
    if not args.smoke:
        ok_pipe_speed = (pipe_qps[2][0] > pipe_qps[1][0]
                         or pipe_qps[2][1] > pipe_qps[1][1])
    print(f"ACCEPTANCE pipelined>serial qps: "
          f"{('PASS' if ok_pipe_speed else 'FAIL') if not args.smoke else 'SKIP (smoke)'} "
          f"(sync {pipe_qps[2][0]:.1f} vs {pipe_qps[1][0]:.1f}, "
          f"queued {pipe_qps[2][1]:.1f} vs {pipe_qps[1][1]:.1f})")
    # ISSUE 6: rank-stability stopping must cut sweeps >= 2x on the
    # slow-rank gadgets at unchanged top-k (deterministic; armed in
    # --smoke — iteration counts, not wall time)
    ok_early = topk_same and it_rank * 2.0 <= it_exact
    print(f"ACCEPTANCE early_exit>=2x: {'PASS' if ok_early else 'FAIL'} "
          f"({it_exact:.1f} -> {it_rank:.1f} sweeps, "
          f"topk {'identical' if topk_same else 'CHANGED'})")
    # ISSUE 6: under overload the SLA queue must shed best-effort traffic
    # (never the guaranteed class), degrade rank_k, resolve shed tickets
    # during admission, and hold the high-priority p95 the collapse queue
    # lets balloon
    sla, col = over["sla"], over["collapse"]
    hi_shed = sla["stats"]["classes"].get(0, {}).get("shed", -1)
    ok_protect = (sla["stats"]["shed"] >= 1 and hi_shed == 0
                  and sla["stats"]["degraded"] >= 1)
    print(f"ACCEPTANCE shed_protects_high: "
          f"{'PASS' if ok_protect else 'FAIL'} "
          f"(shed {sla['stats']['shed']}, class-0 shed {hi_shed}, "
          f"degraded {sla['stats']['degraded']})")
    ok_prompt = sla["shed_prompt"]
    print(f"ACCEPTANCE shed_prompt: {'PASS' if ok_prompt else 'FAIL'} "
          f"(shed tickets resolved during the admission storm)")
    ok_collapse = sla["p95_hi_ms"] < col["p95_hi_ms"]
    print(f"ACCEPTANCE shed_beats_collapse: "
          f"{'PASS' if ok_collapse else 'FAIL'} "
          f"(high-pri p95 {sla['p95_hi_ms']:.1f}ms sla vs "
          f"{col['p95_hi_ms']:.1f}ms collapsed)")
    # ISSUE 7: the queue's reported sheddable-class p95 must equal the
    # served-only bench-side p95 — pre-fix the ~0ms shed resolutions
    # diluted the window and overload *improved* the reported percentile
    rep_p95 = sla["stats"]["classes"].get(1, {}).get("p95_ms")
    ok_window = (sla["p95_lo_served_ms"] is None
                 or (rep_p95 is not None
                     and rep_p95 >= sla["p95_lo_served_ms"] - 1e-6))
    # class 0 is never shed, so its reported window must reproduce the
    # bench-side percentile exactly — a leg that can't go vacuous when
    # overload sheds the whole best-effort class
    rep0 = sla["stats"]["classes"].get(0, {}).get("p95_ms")
    ok_window = (ok_window and rep0 is not None
                 and abs(rep0 - sla["p95_hi_ms"]) <= 1e-6)
    print(f"ACCEPTANCE shed_p95_served_only: "
          f"{'PASS' if ok_window else 'FAIL'} "
          f"(class-1 reported "
          f"{rep_p95 if rep_p95 is None else f'{rep_p95:.1f}'}ms "
          f"vs served-only {sla['p95_lo_served_ms']}ms; class-0 "
          f"{rep0 if rep0 is None else f'{rep0:.1f}'}ms "
          f"vs {sla['p95_hi_ms']:.1f}ms)")
    # ISSUE 8: the ops endpoint must serve a live, consistent snapshot
    # while the queue is mid-burst (armed in --smoke)
    ok_endpoint = ok_health and ok_stats
    print(f"ACCEPTANCE stats_endpoint: {'PASS' if ok_endpoint else 'FAIL'} "
          f"(healthz {'200 ok' if ok_health else 'FAIL'}, stats.json "
          f"{'consistent' if ok_stats else 'INCONSISTENT'})")
    # ISSUE 9: a weight-only delta rolled under live traffic must serve
    # post-delta-correct results (<= 1e-10 vs a cold-built service)
    # without rebuilding surviving plans and without shedding a single
    # guaranteed-class request across the roll
    ok_delta = (ds["l1"] <= 1e-10 and ds["patched"] >= 1
                and ds["built"] == 0 and ds["shed0"] == 0)
    print(f"ACCEPTANCE delta_swap: {'PASS' if ok_delta else 'FAIL'} "
          f"(l1 {ds['l1']:.2e}, {ds['patched']} patched / {ds['built']} "
          f"rebuilt, class-0 shed {ds['shed0']})")
    # ISSUE 10: the lump-reduced sweep must not change the math and must
    # actually shrink the swept matrix on both reducible families (armed
    # in --smoke); the smaller matrix must buy per-sweep time on the
    # duplicate-heavy leg (full runs — smoke shapes are too small to
    # cross pow2 buckets meaningfully)
    ok_lump = all(row["l1"] <= 1e-10 and row["lumped"] >= 1
                  for row in lump.values())
    print(f"ACCEPTANCE lumping_parity: {'PASS' if ok_lump else 'FAIL'} "
          f"(max l1 {max(r['l1'] for r in lump.values()):.2e}, lumped "
          + "/".join(str(r['lumped']) for r in lump.values()) + " rows)")
    dh = lump["duplicate_heavy"]
    ok_lump_speed = args.smoke or dh["us_on"] < dh["us_off"]
    print(f"ACCEPTANCE lumping_per_sweep: "
          f"{('PASS' if ok_lump_speed else 'FAIL') if not args.smoke else 'SKIP (smoke)'} "
          f"(on {dh['us_on']:.1f}us vs off {dh['us_off']:.1f}us)")
    # ISSUE 7: the precision ladder must not change the math — <= 1e-10
    # to the f64 service with every certificate <= the polish tol (armed
    # in --smoke); the bulk dtype must buy >= 2x per-sweep throughput
    # (full runs — smoke graphs are too small to be memory-bound)
    ok_prec_parity = prec_l1 <= 1e-10 and cert_max <= cert_tol
    print(f"ACCEPTANCE precision_parity: "
          f"{'PASS' if ok_prec_parity else 'FAIL'} "
          f"(l1 {prec_l1:.2e}, cert max {cert_max:.2e} <= {cert_tol:.1e})")
    # the 2x gate targets memory-bandwidth-bound sweeps (halve the bytes,
    # halve the time) — on CPU hosts the XLA segment-sum traversal is
    # gather-latency-bound and the dtype narrowing buys less, so like the
    # >=3x batching gate this one only arms where the bound holds
    prec_gated = not args.smoke and jax.default_backend() in ("tpu", "gpu")
    ok_prec_speed = (prec_speed is not None and prec_speed >= 2.0) \
        or not prec_gated
    prec_skip = "smoke" if args.smoke else "cpu host"
    print(f"ACCEPTANCE precision_speedup>=2x: "
          f"{('PASS' if ok_prec_speed else 'FAIL') if prec_gated else f'SKIP ({prec_skip})'} "
          + (f"(f64 {per_sweep['float64']:.1f}us vs f32 "
             f"{per_sweep['float32']:.1f}us per sweep, {prec_speed:.1f}x)"
             if per_sweep is not None else "(smoke: not measured)"))
    return 0 if (ok_speed and ok_match and ok_warm and ok_ladder
                 and ok_queue and ok_plan_hits and ok_plan_latency
                 and ok_pipe_parity and ok_pipe_speed and ok_early
                 and ok_protect and ok_prompt and ok_collapse
                 and ok_window and ok_endpoint and ok_delta
                 and ok_lump and ok_lump_speed
                 and ok_prec_parity and ok_prec_speed) else 1


if __name__ == "__main__":
    raise SystemExit(main())
