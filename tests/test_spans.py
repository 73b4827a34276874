"""Program spans on the profiler's clock (serve.telemetry.span): a queued
burst that hits backpressure, a live graph roll and one whole-graph engine
job, traced with ``jax.profiler`` on the CPU, must leave every span of the
runbook's span table on the host plane, nested as the layers nest and
sharing their ids; and the queue's admission span must count the
backpressure wait."""
import glob
import os
import re
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.engine import RankingEngine
from repro.graph import WebGraphSpec, generate_webgraph
from repro.serve import RankService, RankServiceConfig
from repro.serve.telemetry import MetricsRegistry, span

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUNBOOK = os.path.join(ROOT, "docs", "OPERATIONS.md")
PREFIXES = ("queue.", "pipeline.", "backend.", "engine.", "delta.")


@pytest.fixture(scope="module")
def g():
    return generate_webgraph(WebGraphSpec(900, 7000, 0.5, seed=11))


@pytest.fixture(scope="module")
def queries(g):
    rng = np.random.default_rng(23)
    return [rng.choice(g.n_nodes, size=3, replace=False) for _ in range(12)]


def host_spans(trace_dir):
    """``[name, start, end, line, ids]`` of the program's spans in the
    newest trace under ``trace_dir``; ``line`` stands for the thread."""
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, ln in enumerate(plane.lines):
            out += [[ev.name, ev.start_ns, ev.end_ns, (plane.name, i),
                     dict(ev.stats)] for ev in ln.events
                    if ev.name.startswith(PREFIXES)]
    return out


def documented_spans():
    with open(RUNBOOK) as f:
        text = f.read()
    table = text.split("## Spans", 1)[1].split("\n## ", 1)[0]
    return set(re.findall(r"\*\*([a-z_]+\.[a-z_]+)\*\*", table))


@pytest.fixture(scope="module")
def traced(g, queries, tmp_path_factory):
    """One queued burst (``max_pending`` 2, so ``submit`` blocks), a roll
    that adds a page and one engine job under the profiler, after a
    warm-up that compiles both."""
    svc = RankService(g, RankServiceConfig(v_max=4, tol=1e-10))
    svc.rank(queries[:4])
    RankingEngine(g, n_shards=2).run(tol=1e-8)
    d = str(tmp_path_factory.mktemp("spans-trace"))
    with jax.profiler.trace(d):
        q = svc.queue(max_pending=2)
        tickets = [q.submit(r) for r in queries]
        assert all(t.result(timeout=300) is not None for t in tickets)
        q.close()
        svc.apply_edge_delta(adds=[(0, g.n_nodes)], pages=1)
        res = RankingEngine(g, n_shards=2).run(tol=1e-8)
    return host_spans(d), res


def _inside(inner, outer):
    return (inner[3] == outer[3] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


def test_every_documented_span_is_traced_and_no_other(traced):
    spans, _res = traced
    names = {s[0] for s in spans}
    assert names == documented_spans()


def test_engine_spans_nest_with_job_and_sweep_ids(traced):
    spans, res = traced
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    (build,) = by["engine.build"]
    job = build[4]["job"]
    for name in ("engine.partition", "engine.upload", "engine.order"):
        (inner,) = by[name]
        assert _inside(inner, build) and inner[4] == {"job": job}
    sweeps, syncs = by["engine.sweep"], by["engine.sync"]
    # one host sync per sweep, inside it, carrying its ids
    assert len(sweeps) == len(syncs) == res.iters
    assert [s[4]["sweep"] for s in sweeps] == list(range(1, res.iters + 1))
    for sync in syncs:
        (owner,) = [s for s in sweeps if _inside(sync, s)]
        assert sync[4] == owner[4] == {"job": job,
                                       "sweep": owner[4]["sweep"]}


def test_backend_spans_nest_in_pipeline_sweep_with_batch_ids(traced):
    spans, _res = traced
    sweeps = [s for s in spans if s[0] == "pipeline.sweep"]
    backend = [s for s in spans
               if s[0].startswith("backend.") and s[0] != "backend.order"]
    assert sweeps and len(backend) % 3 == 0 and backend
    for b in backend:
        (owner,) = [s for s in sweeps if _inside(b, s)]
        assert b[4] == owner[4] and set(owner[4]) == {"run", "batch"}
    # a plan built for a batch sorts its edges in the batch's plan stage
    plans = [s for s in spans if s[0] == "pipeline.plan"]
    orders = [s for s in spans if s[0] == "backend.order"]
    assert orders
    for o in orders:
        (owner,) = [s for s in plans if _inside(o, s)]
        assert o[4] == owner[4]
    # each batch's stages share its ids, and the queue's flush wait for a
    # batch carries the same batch index
    runs = {s[4]["run"] for s in sweeps}
    assert len(runs) == 1
    stages = {(s[0], s[4]["batch"]) for s in spans
              if s[0].startswith("pipeline.")}
    batches = {s[4]["batch"] for s in sweeps}
    for j in batches:
        for stage in ("assemble", "plan", "publish"):
            assert (f"pipeline.{stage}", j) in stages
    waits = {s[4]["batch"] for s in spans if s[0] == "queue.flush_wait"}
    assert batches <= waits


def test_delta_spans_nest_in_the_roll(traced):
    spans, _res = traced
    (roll,) = [s for s in spans if s[0] == "delta.roll"]
    inner = [s for s in spans if s[0].startswith("delta.") and s is not roll]
    assert [s[0] for s in inner] == ["delta.apply", "delta.extract",
                                     "delta.swap"]
    assert all(_inside(s, roll) for s in inner)


def test_admit_span_covers_the_backpressure_wait(traced):
    spans, _res = traced
    admits = [s for s in spans if s[0] == "queue.admit"]
    assert len(admits) == 12
    # with two pending at most, some submit waited for a batch to leave
    assert max(e - s for _n, s, e, _l, _i in admits) > 1e6  # over 1 ms


def test_span_feeds_histogram_on_success_and_failure():
    reg = MetricsRegistry()
    h = reg.histogram("x_ms")
    with span("outer.a", h, run=1) as sp:
        assert sp._ann is None  # no profiler session: no annotation
        time.sleep(0.002)
    assert h.count == 1 and h.sum >= 2.0
    assert h.sum == pytest.approx((sp.t1 - sp.t0) * 1e3)
    with pytest.raises(RuntimeError):
        with span("outer.a", h):
            raise RuntimeError("a failed stage still counts")
    assert h.count == 2


def test_queue_admit_ms_and_latency_include_backpressure(g, queries):
    """With one column allowed to wait and the sweep held, a submit
    blocks until the held batches drain: ``queue.admit_ms`` records that
    wait, and the ticket's latency starts at entry to ``submit``, so it
    includes it."""
    svc = RankService(g, RankServiceConfig(v_max=1, tol=1e-10))
    gate = threading.Event()
    sweep = svc.pipeline.sweep

    def held(asm):
        gate.wait(30)
        return sweep(asm)

    svc.pipeline.sweep = held
    q = svc.queue(max_pending=1)
    timer = threading.Timer(0.3, gate.set)
    timer.start()
    calls = []
    for r in queries[:6]:
        t0 = time.perf_counter()
        t = q.submit(r)
        calls.append((time.perf_counter() - t0, t))
    assert all(t.result(timeout=300) is not None for _d, t in calls)
    q.close()
    timer.join(5)
    blocked, ticket = max(calls, key=lambda c: c[0])
    assert blocked > 0.2
    assert ticket.latency_s >= blocked
    snap = q.telemetry_snapshot()
    admit = snap["queue.admit_ms"]
    assert admit["count"] == 6
    assert admit["max"] == pytest.approx(blocked * 1e3, rel=0.05, abs=2.0)
