"""Whole-graph accelerated HITS as the launcher runs it:
``launch/rank.make_engine(g, args).run(tol=...)``.

Set-up builds the configuration's graph, renames its pages by a
permutation drawn from the seed (``graphs.relabel``: the same graph, with
the edge list in another order), and runs one job, which compiles the
engine's programs. The renaming keeps a gain that suits one layout of the
edges only from passing as a gain for the graph; the generator's own
labelling is not used, since it is the fastest layout measured (see
PERF.md). The window runs jobs back to back, one at a time; each job goes
from the graph on the host to the converged authority and hub on the
host, engine build included, since an indexing job pays that on every
crawl. A job that starts inside the window runs to
its end, and the window ends with the last one. Every job's vectors are
held to the reference once the window has closed.
"""
from __future__ import annotations

import hashlib
import time

import numpy as np

from bench import graphs, loadgen, reference, work


class Driver:
    def __init__(self, cfg: dict, mix: dict, *, seed: int, chips: int,
                 control: bool, log):
        if mix.get("outstanding", 1) != 1:
            raise ValueError("whole-graph jobs run one at a time; the mix "
                             f"asks for {mix['outstanding']} in flight")
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.chips, self.control, self.log = chips, control, log

    def setup(self):
        from repro.graph import Graph
        from repro.launch import rank

        cfg = self.cfg
        t = time.perf_counter()
        self.n, self.src, self.dst = graphs.relabel(
            *graphs.build(cfg["graph"]),
            loadgen.rng_for(self.seed, loadgen.RELABEL))
        self.g = Graph(self.n, self.src, self.dst)
        self.log(f"graph: N={self.n} E={len(self.src)} in "
                 f"{time.perf_counter() - t:.2f}s")
        self.args = rank.build_parser().parse_args(cfg["launcher_flags"])
        self.tol = float(cfg["tol"])
        self.log(f"engine: {vars(self.args)}, tol {self.tol:g}")
        t = time.perf_counter()
        self._job()
        self.log(f"warm-up job in {time.perf_counter() - t:.2f}s")

    def _engine(self):
        from repro.launch import rank
        if not self.control:
            return rank.make_engine(self.g, self.args)
        import jax.numpy as jnp
        from repro.core.engine import RankingEngine
        return RankingEngine(self.g, self.args.algorithm,
                             n_shards=self.args.shards,
                             dtype=getattr(jnp, self.cfg["control"]["dtype"]))

    def _job(self):
        t0 = time.perf_counter()
        eng = self._engine()
        t1 = time.perf_counter()
        res = eng.run(tol=self.tol)
        t2 = time.perf_counter()
        return res, (t0, t1, t2)

    def window(self, seconds: float, win) -> dict:
        jobs, spans, results = [], [], {}
        failed = 0
        t0 = win.begin()
        while time.perf_counter() - t0 < seconds:
            try:
                res, (a, b, c) = self._job()
            except Exception as e:  # noqa: BLE001 — counted, then judged
                self.log(f"job failed: {e!r}")
                failed += 1
                continue
            spans += [("engine.build", a, b), ("engine.run", b, c)]
            digest = hashlib.sha1(np.asarray(res.authority).tobytes()
                                  + np.asarray(res.hub).tobytes()).hexdigest()
            results.setdefault(digest, (res.authority, res.hub))
            jobs.append({"build_s": b - a, "total_s": c - a,
                         "sweeps": int(res.iters), "digest": digest,
                         "converged": bool(res.converged)})
        t1 = win.end()
        self.log(f"window: {len(jobs)} jobs, {failed} failed, sweeps "
                 f"{sorted({j['sweeps'] for j in jobs})}, "
                 f"{len(results)} distinct answers")
        return {"jobs": jobs, "answers": results, "attempted":
                len(jobs) + failed, "failed": failed, "host_spans": spans,
                "window_s": t1 - t0}

    def release(self):
        pass

    def check(self, run: dict) -> dict:
        t = time.perf_counter()
        a, h, k = reference.accel_hits(self.n, self.src, self.dst, self.tol)
        self.log(f"reference: {k} sweeps in {time.perf_counter() - t:.2f}s")
        run["ref_sweeps"] = k
        run["n_nodes"], run["n_edges"] = self.n, len(self.src)
        da = max((float(np.abs(x - a).sum()) for x, _ in
                  run["answers"].values()), default=float("inf"))
        dh = max((float(np.abs(y - h).sum()) for _, y in
                  run["answers"].values()), default=float("inf"))
        jobs = run["jobs"]
        dsize = np.dtype(self.cfg["dtype"]).itemsize
        run["useful_bytes"] = len(jobs) * work.graph_bytes(
            self.n, len(self.src), k, dsize)
        lim = self.cfg["limits"]
        return {"jobs_failed": (run["failed"], 0),
                "authority_l1": (da, lim["authority_l1"]),
                "hub_l1": (dh, lim["hub_l1"])}
