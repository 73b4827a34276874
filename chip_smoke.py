#!/usr/bin/env python3
"""Drive the query-ranking service's main paths once on a TPU, and check
every answer against a plain numpy f64 reference written in this file.

  python chip_smoke.py             # phases A-D on one chip
  python chip_smoke.py --chips 4   # only the sharded path, over four chips

Phases, in one process; the first failure ends the run with a non-zero
exit:

A. The served path at the paper's largest graph: ``paper_dataset
   ("stanford")`` (Table 7: 225,441 pages, 2,196,441 links; the
   generator's dedup keeps about 1.26M links) through
   ``RankService.queue()`` with the launcher's defaults (``backend=auto``,
   f64, V=8, caps 32/32), Zipf traffic of 5-root queries.
B. The same service at Kleinberg's query-time sizes: root sets of t=200
   and 50 in-links per root. Kleinberg takes every out-link; the out-link
   cap of 50 here is an assumption. At least two full V=8 batches.
C. Compiled Pallas BSR on ``paper_dataset("britannica")`` (the densest
   stand-in, about 23 links per page after dedup): ``backend=bsr`` at f32
   to the f32 residual floor, without and with the bf16 ladder, the
   program that ran checked for Mosaic's ``tpu_custom_call``; then
   ``auto`` at the default f64 on the same graph, which must serve without
   entering the kernel.
D. Whole-graph accelerated HITS, the paper's own computation, as
   ``launch/rank.py`` builds it, over the back-button stanford graph.

``--chips 4`` runs A and B with ``backend=sharded`` over four devices in
both shard modes, next to the one-device dense service on the same
queries, and nothing else.

Each phase prints its graph and union sizes, the backend each batch took,
wall and compile seconds, and its largest deviation from the reference.
The last line is one JSON object naming the device. Off a TPU, or with
``REPRO_PALLAS_INTERPRET`` set, the script exits 2 before any phase and
prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# per-query L1 limit on f64 paths: the oracle gate of
# benchmarks/serve_rank_bench.py
F64_L1 = 1e-8
# f32 paths, largest per-entry deviation from the f64 reference: the limit
# of tests/test_kernels.py::test_hits_sweep_bsr_full_convergence
F32_MAX_ABS = 1e-4
# the reference iterates until its hub moves less than this (L1)
REF_TOL = 1e-13
SEED = 0


def refuse(why: str):
    print(f"chip_smoke: refusing to run: {why}", file=sys.stderr, flush=True)
    sys.exit(2)


def say(tag: str, msg: str):
    print(f"[{tag}] {msg}", flush=True)


def check(ok: bool, msg: str):
    if not ok:
        raise AssertionError(msg)


# ------------------------------------------------------ host f64 reference


def reference_hits(n: int, src, dst, tol: float, max_iter: int = 100_000):
    """Accelerated HITS (the paper's eq. 2-3 weights) by numpy f64 power
    iteration from the uniform vector over one graph's edges, until the hub
    vector moves at most ``tol`` in L1. Returns the authority of the last
    sweep and the hub, both L1-normalized, and the sweep count."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    indeg = np.bincount(dst, minlength=n).astype(np.float64)
    outdeg = np.bincount(src, minlength=n).astype(np.float64)
    deg = indeg + outdeg
    live = deg > 0
    ca, ch = np.zeros(n), np.zeros(n)
    ca[live] = indeg[live] / deg[live]
    ch[live] = outdeg[live] / deg[live]
    imbalance = np.abs(indeg - outdeg)
    more_in, more_out = indeg > outdeg, indeg < outdeg
    ca[more_in] *= imbalance[more_in]
    ch[more_in] /= imbalance[more_in]
    ca[more_out] /= imbalance[more_out]
    ch[more_out] *= imbalance[more_out]
    h = np.full(n, 1.0 / n)
    for k in range(1, max_iter + 1):
        # (bincount of no edges is int: cast, for an edgeless base set)
        a = np.bincount(dst, weights=(h * ch)[src], minlength=n) * 1.0
        h_new = np.bincount(src, weights=(a * ca)[dst], minlength=n) * 1.0
        h_new /= np.abs(h_new).sum() + 1e-300
        moved = np.abs(h_new - h).sum()
        h = h_new
        if moved <= tol:
            break
    return a / (np.abs(a).sum() + 1e-300), h, k


def reference_for(g, nodes, tol: float = REF_TOL):
    """The reference over the subgraph of ``g`` induced by sorted
    ``nodes`` (a query's base set), in the order of ``nodes``."""
    member = np.zeros(g.n_nodes, bool)
    member[nodes] = True
    keep = member[g.src] & member[g.dst]
    src = np.searchsorted(nodes, g.src[keep])
    dst = np.searchsorted(nodes, g.dst[keep])
    return reference_hits(len(nodes), src, dst, tol)


def deviations(g, results):
    """Per result, (L1 authority, L1 hub, max-abs) against the reference;
    one reference per distinct query."""
    refs, out = {}, []
    for r in results:
        if r.key not in refs:
            refs[r.key] = reference_for(g, r.nodes)
        a, h, _ = refs[r.key]
        da, dh = r.authority - a, r.hub - h
        out.append((np.abs(da).sum(), np.abs(dh).sum(),
                    max(np.abs(da).max(), np.abs(dh).max())))
    return np.array(out)


# --------------------------------------------------------- instrumentation


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and how many
    programs it compiled or found in the persistent cache."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event in self.EVENTS:
            self.seconds += duration
            self.compiles += event == self.EVENTS[-1]

    def _event(self, event, **_kw):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def mark(self):
        return self.seconds, self.compiles, self.cache_hits

    def since(self, mark):
        s, c, h = mark
        return (f"compile {self.seconds - s:.1f}s "
                f"({self.compiles - c} programs compiled, "
                f"{self.cache_hits - h} from the persistent cache)")


class FusedBsrCalls:
    """Counts calls into the fused BSR loop and keeps the last one's
    arguments, so the program that ran can be lowered again and read.
    ``kernels.ops`` looks ``bsr_converge_cols`` up in its module globals,
    which is where this sits; it changes nothing about the call."""

    def __init__(self):
        from repro.kernels import ops
        self.real = ops.bsr_converge_cols
        self.count = 0
        self.last = None
        ops.bsr_converge_cols = self

    def __call__(self, *args, **kwargs):
        self.count += 1
        self.last = (args, kwargs)
        return self.real(*args, **kwargs)

    def program_text(self) -> str:
        args, kwargs = self.last
        return self.real.lower(*args, **kwargs).compile().as_text()


# ------------------------------------------------------------------ phases


def serve(svc, queries):
    """Submit every query to the service's queue at once, as a burst of
    independent clients would; returns results and the queue's stats."""
    with svc.queue() as q:
        tickets = [q.submit(roots) for roots in queries]
        results = [t.result(timeout=900) for t in tickets]
        qstats = q.snapshot_stats()
    return results, qstats


def served_phase(tag, g, cfg, queries, clock, *, f32=False):
    """Serve ``queries`` on a fresh service over ``g`` and hold every
    answer to the reference. Returns (results, service, queue stats)."""
    from repro.serve import RankService

    mark, t0 = clock.mark(), time.perf_counter()
    svc = RankService(g, cfg)
    results, qs = serve(svc, queries)
    wall = time.perf_counter() - t0
    s = svc.snapshot_stats()
    tel = svc.telemetry_snapshot()
    un, ue = tel["service.union.nodes"], tel["service.union.edges"]
    say(tag, f"{len(queries)} requests, {len(set(r.key for r in results))} "
             f"distinct: {s['hit']} hit / {s['warm']} warm / {s['cold']} "
             f"cold; {qs['batches']} queue batches ({qs['flush_vmax']} "
             f"full at V={cfg.v_max})")
    say(tag, f"unions: {un['count']} swept, nodes max {un['max']:.0f} "
             f"p50 {un['p50']:.0f}, edges max {ue['max']:.0f} "
             f"p50 {ue['p50']:.0f}")
    say(tag, f"backend batches {s['backend_batches']}, dtype "
             f"{np.dtype(cfg.dtype).name}, tol {svc.cfg.tol:g}")
    say(tag, f"wall {wall:.1f}s, {clock.since(mark)}")
    dev = deviations(g, results)
    if f32:
        worst = dev[:, 2].max()
        say(tag, f"max |score - f64 reference| {worst:.3e} (limit "
                 f"{F32_MAX_ABS:g}); max L1 authority {dev[:, 0].max():.3e}"
                 f" hub {dev[:, 1].max():.3e}")
        check(worst <= F32_MAX_ABS, f"{tag}: f32 deviation {worst:.3e}")
    else:
        worst = dev[:, :2].max()
        say(tag, f"max L1 vs f64 reference: authority {dev[:, 0].max():.3e}"
                 f" hub {dev[:, 1].max():.3e} (limit {F64_L1:g})")
        check(worst <= F64_L1, f"{tag}: L1 deviation {worst:.3e}")
    return results, svc, qs


def a_traffic(g):
    """Launcher-style traffic: Zipf-popular 5-root queries."""
    from repro.launch.serve_rank import zipf_query_stream
    return zipf_query_stream(np.random.default_rng(SEED), g.n_nodes, 48, 5)


def b_traffic(g, base):
    """Kleinberg's sizes: 16 distinct root sets of t=200 (text-match
    stand-ins), caps of 50 in-links and (assumed) 50 out-links per root."""
    rng = np.random.default_rng(SEED + 1)
    queries = [rng.choice(g.n_nodes, size=200, replace=False)
               for _ in range(16)]
    return dataclasses.replace(base, out_cap=50, in_cap=50), queries


def phase_a(g, base, clock):
    say("A", f"stanford N={g.n_nodes} E={g.n_edges} dangling "
             f"{g.dangling_fraction():.1%}; launcher defaults")
    served_phase("A", g, base, a_traffic(g), clock)


def phase_b(g, base, clock):
    cfg, queries = b_traffic(g, base)
    say("B", "Kleinberg sizes: t=200 roots, <=50 in-links per root, "
             "<=50 out-links per root (assumed; Kleinberg takes all)")
    qs = served_phase("B", g, cfg, queries, clock)[2]
    check(qs["flush_vmax"] >= 2,
          f"B: {qs['flush_vmax']} full V={cfg.v_max} batches, want 2")


def phase_c(g, base, clock):
    import jax.numpy as jnp
    from repro.launch.serve_rank import zipf_query_stream
    from repro.serve.backends import dtype_floor

    say("C", f"britannica N={g.n_nodes} E={g.n_edges} (average degree "
             f"{g.n_edges / g.n_nodes:.1f})")
    queries = zipf_query_stream(np.random.default_rng(SEED + 2), g.n_nodes,
                                32, 5)
    kernel = FusedBsrCalls()
    f32 = dataclasses.replace(base, backend="bsr", dtype=jnp.float32,
                              tol=dtype_floor(jnp.float32))
    for tag, cfg in (("C/bsr-f32", f32),
                     ("C/bsr-f32-bf16-ladder",
                      dataclasses.replace(f32, sweep_dtype="bf16"))):
        before = kernel.count
        svc = served_phase(tag, g, cfg, queries, clock, f32=True)[1]
        check(set(svc.snapshot_stats()["backend_batches"]) == {"bsr"},
              f"{tag}: not every batch ran bsr")
        check(kernel.count > before, f"{tag}: the fused BSR loop never ran")
        mosaic = "tpu_custom_call" in kernel.program_text()
        say(tag, f"compiled program contains tpu_custom_call: {mosaic}")
        check(mosaic, f"{tag}: the BSR program has no Mosaic kernel")
    before = kernel.count
    svc = served_phase("C/auto-f64", g, base, queries, clock)[1]
    batches = svc.snapshot_stats()["backend_batches"]
    check(set(batches) == {"dense"}, f"C/auto-f64: batches {batches}")
    check(kernel.count == before, "C/auto-f64: f64 entered the BSR kernel")
    say("C/auto-f64", "served without entering the Mosaic kernel")


def phase_d(g, clock):
    from repro.core import back_button
    from repro.launch import rank

    args = rank.build_parser().parse_args(
        ["--dataset", "stanford", "--scale", "1.0", "--backbutton"])
    gb = back_button(g)
    say("D", f"back-button stanford N={gb.n_nodes} E={gb.n_edges}, "
             f"{args.shards} virtual shards, tol {args.tol:g}")
    mark, t0 = clock.mark(), time.perf_counter()
    res = rank.make_engine(gb, args).run(tol=args.tol)
    wall = time.perf_counter() - t0
    say("D", f"converged={res.converged} after {res.iters} sweeps, "
             f"residual {res.residuals[-1]:.3e}; wall {wall:.1f}s, "
             f"{clock.since(mark)}")
    check(res.converged, "D: the engine did not converge")
    a, h, k = reference_hits(gb.n_nodes, gb.src, gb.dst, args.tol)
    da = np.abs(res.authority - a).sum()
    dh = np.abs(res.hub - h).sum()
    say("D", f"reference: {k} sweeps; L1 authority {da:.3e} hub {dh:.3e} "
             f"(limit {F64_L1:g})")
    check(max(da, dh) <= F64_L1, f"D: L1 deviation {max(da, dh):.3e}")


def four_chips(g, base, clock, n_chips: int):
    """A and B through the sharded backend over ``n_chips`` devices, in
    both shard modes, against the one-device dense service and the
    reference."""
    import jax

    say("4chip", f"stanford N={g.n_nodes} E={g.n_edges}; devices "
                 f"{[d.id for d in jax.devices()[:n_chips]]}")
    for name, (cfg, queries) in (("A", (base, a_traffic(g))),
                                 ("B", b_traffic(g, base))):
        dense = served_phase(f"{name}/dense-1chip", g,
                             dataclasses.replace(cfg, backend="dense"),
                             queries, clock)[0]
        for mode in ("replicated", "dual_blocked"):
            tag = f"{name}/sharded-{mode}"
            cfg_s = dataclasses.replace(cfg, backend="sharded",
                                        shard_mode=mode,
                                        shard_devices=n_chips)
            results, svc, _qs = served_phase(tag, g, cfg_s, queries,
                                             clock)
            check(set(svc.snapshot_stats()["backend_batches"])
                  == {"sharded"}, f"{tag}: not every batch was sharded")
            l1 = max(max(np.abs(r.authority - o.authority).sum(),
                         np.abs(r.hub - o.hub).sum())
                     for r, o in zip(results, dense))
            say(tag, f"max L1 vs one-chip dense: {l1:.3e} (limit "
                     f"{F64_L1:g})")
            check(l1 <= F64_L1, f"{tag}: L1 vs dense {l1:.3e}")
            plans = svc._plans.plans()
            held = {d.id for p in plans for x in p.eargs
                    for d in x.sharding.device_set}
            split = all(not x.sharding.is_fully_replicated
                        for p in plans for x in p.eargs)
            say(tag, f"{len(plans)} plans; edge planes on devices "
                     f"{sorted(held)}, each split across them: {split}")
            check(len(held) == n_chips and split,
                  f"{tag}: plans not spread over {n_chips} devices")
            in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                      for d in jax.devices()[:n_chips]]
            say(tag, f"device bytes in use {in_use}")


# -------------------------------------------------------------------- main


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded path across four chips")
    args = ap.parse_args()

    if os.environ.get("REPRO_PALLAS_INTERPRET"):
        refuse("REPRO_PALLAS_INTERPRET is set; the chip path is compiled")
    if not (ROOT / "src" / "repro").is_dir():
        refuse(f"no repository beside this script ({ROOT / 'src'})")
    import jax
    jax.config.update("jax_enable_x64", True)  # as every entry point has it
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        refuse(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < args.chips:
        refuse(f"--chips {args.chips} but JAX sees {len(devices)} device(s)")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.graph import paper_dataset
    from repro.launch import serve_rank
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    base = serve_rank.service_config(serve_rank.build_parser().parse_args([]))
    t0 = time.perf_counter()
    stanford = paper_dataset("stanford", scale=1.0)
    say("setup", f"stanford generated in {time.perf_counter() - t0:.1f}s")
    if args.chips > 1:
        four_chips(stanford, base, clock, args.chips)
    else:
        phase_a(stanford, base, clock)
        phase_b(stanford, base, clock)
        phase_c(paper_dataset("britannica", scale=1.0), base, clock)
        phase_d(stanford, clock)
    print(f"compile cache: {cache_dir}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
