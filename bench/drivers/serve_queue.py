"""Query-time HITS through the served path: ``RankService.queue()``.

Set-up builds the configuration's graph on the host, a service with the
launcher's default settings and the settings the configuration states,
then serves warm-up batches of the mix's ``warmup_widths``, so every
program the window needs is compiled and the warm-start table is in
steady state, then one batch for each padded shape that candidate batches
of the mix's ``warmup_cover_widths`` reach.

The window sends the mix's requests to ``RankQueue.submit`` on a Poisson
schedule (open loop), the same for every seed, until its time is up, and
closes once every request sent has been answered. Each is timed from when
it was due to when the benchmark's own clock (``loadgen.ResolveClock``)
saw its ticket answered.
Once the window has closed, every answer is held to the reference: its
base set exactly, its authority and hub vectors in L1.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import numpy as np

from bench import graphs, loadgen, reference, work


def _flat(snapshot: dict) -> dict:
    """{(family, label): value or (count, sum)} of a registry snapshot."""
    out = {}
    for name, v in snapshot.items():
        items = v.items() if isinstance(v, dict) and "count" not in v \
            else [(None, v)]
        for lbl, x in items:
            if isinstance(x, dict):
                out[(name, lbl)] = (x["count"], x["sum"])
            elif isinstance(x, (int, float)):
                out[(name, lbl)] = x
    return out


def telemetry_delta(before: dict, after: dict) -> dict:
    """What each counter and histogram gained between two snapshots."""
    b, a = _flat(before), _flat(after)
    out = {}
    for k, v in a.items():
        old = b.get(k, (0, 0.0) if isinstance(v, tuple) else 0)
        out[k] = (v[0] - old[0], v[1] - old[1]) if isinstance(v, tuple) \
            else v - old
    return out


class Driver:
    def __init__(self, cfg: dict, mix: dict, *, seed: int, chips: int,
                 control: bool, log):
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.chips, self.control, self.log = chips, control, log
        self.q = self.svc = None

    # -- set-up -----------------------------------------------------------

    def setup(self):
        import jax.numpy as jnp
        from repro.graph import Graph
        from repro.launch import serve_rank
        from repro.serve import RankService

        cfg, mix = self.cfg, self.mix
        if mix.get("loop", "open") != "open":
            raise ValueError(f"queries are sent in an open loop; the mix "
                             f"asks for {mix['loop']!r}")
        t = time.perf_counter()
        self.n, self.src, self.dst = graphs.build(cfg["graph"])
        self.g = Graph(self.n, self.src, self.dst)
        self.log(f"graph: N={self.n} E={len(self.src)} in "
                 f"{time.perf_counter() - t:.2f}s")
        stated = dict(cfg["service"])
        if self.control:
            stated.update(cfg["control"])
        stated["dtype"] = getattr(jnp, stated["dtype"])
        base = serve_rank.service_config(serve_rank.build_parser()
                                         .parse_args([]))
        self.svc_cfg = dataclasses.replace(base, shard_devices=self.chips,
                                           **stated)
        self.log(f"service: {self.svc_cfg}")
        indeg = np.bincount(self.dst, minlength=self.n)
        roots = cfg["query"]["roots"]
        self.requests = loadgen.Requests(mix, indeg, roots, self.seed)
        warm = loadgen.Requests(mix, indeg, roots, self.seed,
                                loadgen.WARMUP)
        self.svc = RankService(self.g, self.svc_cfg)
        t = time.perf_counter()
        widths = [w for w in mix["warmup_widths"] if w <= self.svc_cfg.v_max]
        for _ in range(int(mix["warmup_rounds"])):  # fills the warm table
            for w in widths:
                self.svc.rank(warm.take(w))
        cover = self._shape_cover(warm, mix["warmup_cover_widths"],
                                  int(mix["warmup_cover"]))
        for batch in cover:
            self.svc.rank(batch)
        self.log(f"warm-up: {mix['warmup_rounds']} rounds of widths {widths}"
                 f", then {len(cover)} batches for the padded shapes, in "
                 f"{time.perf_counter() - t:.2f}s")

    def _shape_cover(self, warm, widths, per_width: int) -> list:
        """One batch for each padded shape that ``per_width`` candidate
        batches of each of ``widths`` reach. The service pads a union to
        power-of-two node (plus a dead row) and edge counts, and compiles
        each shape once; the window's batches, whose widths follow the
        arrivals, may take any of them, some rarely (a width-2 union with
        many pages and few links: ~6% of width-2 batches)."""
        q = self.cfg["query"]
        ref = reference.Index(self.n, self.src, self.dst)

        def pad(x):
            return 1 << (max(int(x), 16) - 1).bit_length()

        out = {}
        for w in widths:
            for _ in range(per_width):
                batch = warm.take(w)
                nodes = np.unique(np.concatenate(
                    [ref.base_set(r, q["out_cap"], q["in_cap"])
                     for r in batch]))
                shape = (pad(len(nodes) + 1), pad(ref.induced_count(nodes)))
                out.setdefault(shape, batch)
        self.log(f"padded shapes reached: {sorted(out)}")
        return list(out.values())

    # -- window -----------------------------------------------------------

    def _snapshots(self):
        return self.svc.telemetry_snapshot(), self.q.telemetry_snapshot()

    def window(self, seconds: float, win) -> dict:
        self.q = self.svc.queue()
        before = self._snapshots()
        run = self._open(seconds, win)
        after = self._snapshots()
        run["svc_delta"] = telemetry_delta(before[0], after[0])
        run["queue_delta"] = telemetry_delta(before[1], after[1])
        run["host_spans"] = [(stage, t0, t1) for _r, _j, stage, t0, t1
                             in list(self.svc.pipeline.trace)
                             if t1 >= win.t0 and t0 <= win.t1]
        self._summarise(run)
        return run

    @staticmethod
    def _outcome(ticket):
        """The ticket's result, or None for a request that failed, was
        shed, or never came."""
        if not ticket.done():
            return None
        try:
            r = ticket.result(timeout=0)
        except Exception:  # noqa: BLE001 — a failed request, not a crash
            return None
        return None if r is None or r.status == "shed" else r

    def _open(self, seconds: float, win) -> dict:
        # the same arrivals for every seed; the seed draws the root sets.
        # Near capacity, which requests share a batch follows the arrivals:
        # drawn per seed, they moved the median latency by half between
        # seeds on a TPU v5e, against a few percent between runs of one seed
        due = loadgen.poisson_schedule(
            loadgen.rng_for(0, loadgen.SCHEDULE),
            float(self.mix["rate_qps"]), seconds)
        reqs = self.requests.take(len(due))
        sent, tickets = [], []
        clock = loadgen.ResolveClock()
        t0 = win.begin()
        for i, (d, roots) in enumerate(zip(due, reqs)):
            wait = t0 + d - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            # above capacity the queue's backpressure holds ``submit``:
            # once the window's time is up, nothing more is sent
            if time.perf_counter() >= t0 + seconds:
                break
            sent.append(time.perf_counter())
            tickets.append(self.q.submit(roots))
            clock.watch(i, tickets[-1])
        # the window closes once every request sent has been answered
        stamps = clock.close(t0 + seconds + 60.0)
        win.end()
        n_due = len(due)
        due, reqs = due[:len(sent)], reqs[:len(sent)]
        results = [self._outcome(t) if i in stamps else None
                   for i, t in enumerate(tickets)]
        lat = loadgen.latencies_ms(
            t0 + due, [stamps[i] if r is not None else None
                       for i, r in enumerate(results)])
        # the program's own resolve instant, as a cross-check of the clock
        gap = [1e3 * (stamps[i] - t.resolved_at) for i, t in
               enumerate(tickets) if i in stamps and t.resolved_at]
        if gap:
            self.log(f"resolve clock: stamps trail the program's resolve "
                     f"instant by {np.median(gap):.3f} ms median, "
                     f"{min(gap):.3f} to {max(gap):.3f} ms")
        ok = sum(r is not None for r in results)
        self.log(f"sent {len(sent)} of {n_due} due, {ok} answered")
        return {"seconds": seconds, "requests": reqs, "results": results,
                "latencies_ms": lat, "answered": ok,
                "lateness_ms": loadgen.lateness_ms(t0 + due, sent),
                "attempted": len(due), "failed": len(due) - ok,
                "unanswered": len(due) - len(stamps),
                "window_s": win.t1 - win.t0}

    def _summarise(self, run: dict):
        d = run["svc_delta"]
        served = [r for r in run["results"] if r is not None]
        statuses = collections.Counter(r.status for r in served)
        backends = {lbl: v for (n, lbl), v in d.items()
                    if n == "service.backend.batches" and v}
        un, ue = d.get(("service.union.nodes", None), (0, 0.0)), \
            d.get(("service.union.edges", None), (0, 0.0))
        self.log(f"window: {run['attempted']} sent, {run['failed']} failed, "
                 f"statuses {dict(statuses)}, backend batches {backends}")
        if un[0]:
            self.log(f"unions: {un[0]} swept, mean {un[1] / un[0]:.0f} nodes,"
                     f" {ue[1] / ue[0]:.0f} edges")
        q = d.get(("service.queries", None), 0)
        if q:
            hits = d.get(("service.cache.hit", None), 0)
            self.log(f"beta {self.mix.get('popularity_beta')}: hit share "
                     f"{100.0 * hits / q:.1f}% of {q} queries")

    # -- after the window -------------------------------------------------

    def release(self):
        if self.q is not None:
            self.q.close()
        self.q = self.svc = None

    def check(self, run: dict) -> dict:
        q = self.cfg["query"]
        lim = self.cfg["limits"]
        ref = reference.Index(self.n, self.src, self.dst)
        t = time.perf_counter()
        differ, da, dh = 0, 0.0, 0.0
        refs, seen = {}, set()  # one reference per root set
        for i, r in enumerate(run["results"]):
            if r is None or id(r) in seen:  # coalesced requests share one
                continue
            seen.add(id(r))
            if r.key not in refs:
                refs[r.key] = ref.rank_query(run["requests"][i],
                                             q["out_cap"], q["in_cap"])
            nodes, a, h = refs[r.key]
            if len(nodes) != len(r.nodes) or (nodes != r.nodes).any():
                differ += 1
                continue
            da = max(da, float(np.abs(r.authority - a).sum()))
            dh = max(dh, float(np.abs(r.hub - h).sum()))
        self.log(f"reference: {len(seen)} answers, {len(refs)} root sets, "
                 f"checked in {time.perf_counter() - t:.2f}s")
        run["useful_bytes"] = self._useful_bytes(run, ref)
        return {"unanswered": (run["unanswered"], 0),
                "base_sets_differ": (differ, 0),
                "authority_l1": (da, lim["authority_l1"]),
                "hub_l1": (dh, lim["hub_l1"])}

    def _useful_bytes(self, run: dict, ref) -> int | None:
        """Useful bytes of every query swept in a traced window."""
        if run.get("trace") is None:
            return None
        dsize = np.dtype(self.svc_cfg.dtype).itemsize
        total, seen = 0, set()
        for r in run["results"]:
            if r is None or r.status == "hit" or id(r) in seen:
                continue
            seen.add(id(r))
            s, _d = ref.induced(r.nodes.astype(np.int64))
            total += work.query_bytes(len(r.nodes), len(s), r.iters, dsize)
        return total
