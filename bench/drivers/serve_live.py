"""Query-time HITS through the served path while a crawler changes the
index: ``RankService.queue()`` beside ``launch.serve_rank.roll_delta``.

Set-up is the ``serve_queue`` driver's (graph, service, warm-up, one
batch per padded shape), then one roll of the configuration's crawl feed,
with a page, so the window starts on a graph the crawler has changed.

The feed (``Feed``) is drawn from the run's seed before the window: each
roll adds ``links_added`` links and removes ``links_removed``, and a roll
listed in ``page_rolls`` adds a page, given one in-link and one out-link
among its links. A link's source is a page with out-links, drawn by
out-degree; its destination is drawn by popularity, in-degree + 1; the
removed link is drawn uniformly. The window is the ``serve_queue``
driver's open loop, plus a crawler thread that rolls the feed in at
``first_roll_s + k * roll_every_s`` from the window's start (at most
``window_rolls`` of them, and only those that fall inside the window),
the same times for every seed. Each request records the last graph
version acknowledged before its submit.

Once the window has closed, every answer is held to the reference on the
graph version it is stamped with: its base set exactly, its authority and
hub vectors in L1. ``stale_serves`` counts answers stamped with a version
older than the one acknowledged before their submit, ``refused`` the
submits that raised, and ``rolls_failed`` the rolls that raised,
acknowledged another version than the next, or never ran.

A program whose answers carry no ``graph_version`` cannot serve this
cell: set-up says so and stops before it builds anything.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from bench import loadgen, reference, work
from bench.drivers import serve_queue

CRAWL = 6  # the loadgen stream number of the crawl feed's draws
SHIFT = 32  # a link's key is src << SHIFT | dst: new pages keep it valid


class Feed:
    """The crawl feed of one run: ``deltas[k]`` is the k-th roll's
    changeset (0 is set-up's), drawn against the graph as the rolls
    before it left it; ``versions()`` walks the graph's edge lists."""

    def __init__(self, n: int, src, dst, crawl: dict, seed: int):
        self.n0 = int(n)
        self.keys0 = np.sort(np.asarray(src, np.int64) << SHIFT
                             | np.asarray(dst, np.int64))
        self.crawl = crawl
        rng = loadgen.rng_for(seed, CRAWL)
        n, keys = self.n0, self.keys0
        outdeg = np.bincount(src, minlength=n).astype(np.float64)
        indeg = np.bincount(dst, minlength=n).astype(np.float64)
        self.deltas = []
        for k in range(1 + int(crawl["window_rolls"])):
            pages = int(k in crawl["page_rolls"])
            delta = self._draw(rng, n, keys, outdeg, indeg, pages)
            self.deltas.append(delta)
            n, keys = self._apply(n, keys, delta)
            outdeg = np.concatenate([outdeg, np.zeros(pages)])
            indeg = np.concatenate([indeg, np.zeros(pages)])
            for (s, d), sign in [(a, 1) for a in delta["adds"]] \
                    + [(r, -1) for r in delta["removes"]]:
                outdeg[s] += sign
                indeg[d] += sign

    def times(self, seconds: float) -> list:
        """Seconds from the window's start of its rolls."""
        c = self.crawl
        out = [c["first_roll_s"] + k * c["roll_every_s"]
               for k in range(int(c["window_rolls"]))]
        return [t for t in out if t < seconds]

    def _draw(self, rng, n, keys, outdeg, indeg, pages) -> dict:
        by_out, by_pop = np.cumsum(outdeg), np.cumsum(indeg + 1.0)

        def pick(cdf):
            return int(np.searchsorted(cdf, rng.random() * cdf[-1],
                                       side="right"))

        links = []
        for p in range(n, n + pages):  # one in-link and one out-link
            links += [(pick(by_out), p), (p, pick(by_pop))]
        while len(links) < int(self.crawl["links_added"]):
            s, d = pick(by_out), pick(by_pop)
            key = s << SHIFT | d
            pos = np.searchsorted(keys, key)
            if s == d or (s, d) in links or (pos < len(keys)
                                             and keys[pos] == key):
                continue
            links.append((s, d))
        gone = rng.choice(len(keys), size=int(self.crawl["links_removed"]),
                          replace=False)
        removes = [(int(keys[i] >> SHIFT), int(keys[i] & (1 << SHIFT) - 1))
                   for i in gone]
        return {"adds": links, "removes": removes, "pages": pages}

    @staticmethod
    def _apply(n, keys, delta):
        def key(pairs):
            return np.array([s << SHIFT | d for s, d in pairs], np.int64)

        keys = np.setdiff1d(keys, key(delta["removes"]), assume_unique=True)
        add = np.sort(key(delta["adds"]))
        return n + delta["pages"], np.insert(keys, np.searchsorted(keys, add),
                                             add)

    def versions(self):
        """``(version, n, src, dst)`` for version 0 (the configuration's
        graph) and after each roll of the feed."""
        n, keys = self.n0, self.keys0
        for v in range(len(self.deltas) + 1):
            yield v, n, keys >> SHIFT, keys & (1 << SHIFT) - 1
            if v < len(self.deltas):
                n, keys = self._apply(n, keys, self.deltas[v])


class _Refused:
    """The ticket of a submit that raised: answered with its error."""

    resolved_at = None

    def __init__(self, exc):
        self.exc = exc

    def done(self):
        return True

    def result(self, timeout=None):
        raise self.exc


class Driver(serve_queue.Driver):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.acked = None
        self.rolls, self.roll_errors = [], []

    # -- set-up -----------------------------------------------------------

    def setup(self):
        from repro.serve.rank_service import QueryResult
        if "graph_version" not in QueryResult.__dataclass_fields__:
            raise RuntimeError("the program's answers carry no "
                               "graph_version: it cannot serve a live "
                               "crawl")
        super().setup()
        t = time.perf_counter()
        self.feed = Feed(self.n, self.src, self.dst, self.cfg["crawl"],
                         self.seed)
        self.base_version = self.acked = self.svc.graph_version
        self.log(f"crawl feed: {len(self.feed.deltas)} rolls drawn in "
                 f"{time.perf_counter() - t:.2f}s")
        self._roll(0)
        if self.roll_errors:
            raise RuntimeError(f"set-up roll failed: {self.roll_errors[0]}")
        self.log(f"set-up roll: version {self.acked}, "
                 f"{self.rolls[0][2]:.1f} ms")

    def _roll(self, k: int):
        from repro.launch import serve_rank
        t = time.perf_counter()
        try:
            ack = serve_rank.roll_delta(self.svc, self.feed.deltas[k])
        except Exception as e:  # noqa: BLE001 — a failed roll, counted
            self.roll_errors.append(f"roll {k}: {e!r}")
            return
        if ack["version"] != self.base_version + k + 1:
            self.roll_errors.append(f"roll {k}: acknowledged version "
                                    f"{ack['version']}, want "
                                    f"{self.base_version + k + 1}")
        self.rolls.append((k, ack["version"],
                           (time.perf_counter() - t) * 1e3))
        self.acked = ack["version"]

    # -- window -----------------------------------------------------------

    def window(self, seconds: float, win) -> dict:
        run = super().window(seconds, win)
        run["host_spans"] += [("delta.roll", t0, t1) for _v, stage, t0, t1
                              in list(self.svc.delta_trace)
                              if stage == "roll" and t1 >= win.t0
                              and t0 <= win.t1]
        return run

    def _crawler(self, t0: float, times: list):
        for k, at in enumerate(times, start=1):
            wait = t0 + at - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self._roll(k)

    def _open(self, seconds: float, win) -> dict:
        # the serve_queue driver's open loop, beside the crawler
        due = loadgen.poisson_schedule(
            loadgen.rng_for(0, loadgen.SCHEDULE),
            float(self.mix["rate_qps"]), seconds)
        reqs = self.requests.take(len(due))
        times = self.feed.times(seconds)
        sent, tickets, acked, refused = [], [], [], 0
        clock = loadgen.ResolveClock()
        t0 = win.begin()
        crawler = threading.Thread(target=self._crawler, args=(t0, times),
                                   daemon=True, name="bench-crawler")
        crawler.start()
        for i, (d, roots) in enumerate(zip(due, reqs)):
            wait = t0 + d - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            if time.perf_counter() >= t0 + seconds:
                break
            sent.append(time.perf_counter())
            acked.append(self.acked)
            try:
                tickets.append(self.q.submit(roots))
            except Exception as e:  # noqa: BLE001 — a refusal, counted
                refused += 1
                tickets.append(_Refused(e))
            clock.watch(i, tickets[-1])
        stamps = clock.close(t0 + seconds + 60.0)
        crawler.join(max(t0 + seconds + 60.0 - time.perf_counter(), 0.0))
        win.end()
        n_due = len(due)
        due, reqs = due[:len(sent)], reqs[:len(sent)]
        results = [self._outcome(t) if i in stamps else None
                   for i, t in enumerate(tickets)]
        lat = loadgen.latencies_ms(
            t0 + due, [stamps[i] if r is not None else None
                       for i, r in enumerate(results)])
        ok = sum(r is not None for r in results)
        done = [k for k, _v, _ms in self.rolls if k >= 1]
        self.log(f"sent {len(sent)} of {n_due} due, {ok} answered, "
                 f"{refused} refused; rolls {done} of {len(times)}, "
                 f"ms {[round(ms, 1) for k, _v, ms in self.rolls if k]}")
        return {"seconds": seconds, "requests": reqs, "results": results,
                "latencies_ms": lat, "answered": ok,
                "lateness_ms": loadgen.lateness_ms(t0 + due, sent),
                "attempted": len(due), "failed": len(due) - ok,
                "unanswered": len(due) - len(stamps), "refused": refused,
                "acked": acked, "rolls_missed": len(times) - len(done),
                "window_s": win.t1 - win.t0}

    # -- after the window -------------------------------------------------

    def check(self, run: dict) -> dict:
        q, lim = self.cfg["query"], self.cfg["limits"]
        t = time.perf_counter()
        stale = sum(r is not None and r.graph_version < a for r, a in
                    zip(run["results"], run["acked"]))
        by_version, seen = {}, set()  # coalesced requests share an answer
        for i, r in enumerate(run["results"]):
            if r is None or id(r) in seen:
                continue
            seen.add(id(r))
            by_version.setdefault(int(r.graph_version), []).append(i)
        differ, da, dh, refs = 0, 0.0, 0.0, 0
        # useful bytes of every query swept in a traced window, each on
        # its own version (sweep_roofline)
        traced, moved = run.get("trace") is not None, 0
        dsize = np.dtype(self.svc_cfg.dtype).itemsize
        for v, n, src, dst in self.feed.versions():
            if v not in by_version:
                continue
            ref = reference.Index(n, src, dst)
            done = {}  # one reference per root set and version
            for i in by_version.pop(v):
                r = run["results"][i]
                if traced and r.status != "hit":
                    moved += work.query_bytes(
                        len(r.nodes), ref.induced_count(r.nodes.astype(
                            np.int64)), r.iters, dsize)
                if r.key not in done:
                    done[r.key] = ref.rank_query(run["requests"][i],
                                                 q["out_cap"], q["in_cap"])
                nodes, a, h = done[r.key]
                if len(nodes) != len(r.nodes) or (nodes != r.nodes).any():
                    differ += 1
                    continue
                da = max(da, float(np.abs(r.authority - a).sum()))
                dh = max(dh, float(np.abs(r.hub - h).sum()))
            refs += len(done)
        # an answer stamped with a version the feed never made
        differ += sum(len(v) for v in by_version.values())
        run["useful_bytes"] = moved if traced else None
        self.log(f"reference: {len(seen)} answers, {refs} root sets over "
                 f"their versions, checked in "
                 f"{time.perf_counter() - t:.2f}s")
        return {"unanswered": (run["unanswered"], 0),
                "base_sets_differ": (differ, 0),
                "authority_l1": (da, lim["authority_l1"]),
                "hub_l1": (dh, lim["hub_l1"]),
                "stale_serves": (stale, lim["stale_serves"]),
                "refused": (run["refused"], lim["refused"]),
                "rolls_failed": (len(self.roll_errors) + run["rolls_missed"],
                                 lim["rolls_failed"])}
