"""Serving telemetry layer + ops hardening (serve.telemetry, RankQueue
drain, launch.serve_rank SIGTERM path): registry semantics, the legacy
stats-dict alias views, the /healthz + /stats.json endpoint contract,
the runbook-consistency gate (every emitted metric family must be
documented in docs/OPERATIONS.md — and every documented family must
exist), drain-under-load, and the launcher's graceful-drain exit."""
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.graph import WebGraphSpec, generate_webgraph
from repro.serve import (MetricsRegistry, RankService, RankServiceConfig,
                         StatsServer)
from repro.serve.telemetry import (LabeledView, LegacyStatsDict,
                                   render_json)

TOL = 1e-12
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUNBOOK = os.path.join(ROOT, "docs", "OPERATIONS.md")


@pytest.fixture(scope="module")
def g():
    return generate_webgraph(WebGraphSpec(900, 7000, 0.5, seed=11))


@pytest.fixture(scope="module")
def queries(g):
    rng = np.random.default_rng(17)
    return [rng.choice(g.n_nodes, size=3, replace=False) for _ in range(8)]


def svc_for(g, **kw):
    kw.setdefault("v_max", 4)
    kw.setdefault("tol", TOL)
    return RankService(g, RankServiceConfig(**kw))


# ------------------------------------------------------- registry units


def test_counter_semantics():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(3)
    assert c.value == 4
    c.set(10)  # mirrored-ledger idiom
    assert c.value == 10
    d = {"k": reg.counter("d")}
    d["k"] += 2  # __iadd__ keeps the dict-of-metric call-site idiom
    assert reg.counter("d").value == 2
    assert reg.counter("c") is c  # get-or-create returns the same object


def test_gauge_set_and_ratchet():
    reg = MetricsRegistry()
    gge = reg.gauge("g")
    gge.set(5)
    gge.max(3)  # ratchet never lowers
    assert gge.value == 5
    gge.max(9)
    assert gge.value == 9


def test_histogram_window_vs_lifetime():
    reg = MetricsRegistry()
    h = reg.histogram("h", window=4)
    assert h.percentile(50) is None  # empty reservoir
    for v in range(1, 11):
        h.observe(v)
    # lifetime totals are exact; percentiles see only the newest window
    assert h.count == 10 and h.sum == 55 and h.min == 1 and h.max == 10
    assert h.percentile(50) == pytest.approx(8.5)  # over [7, 8, 9, 10]
    s = h.summary()
    assert set(s) == {"count", "sum", "min", "max", "p50", "p95", "p99"}
    assert s["count"] == 10 and s["p50"] == pytest.approx(8.5)


def test_family_kind_conflict_and_labels():
    reg = MetricsRegistry()
    reg.counter("x", "a")
    reg.counter("x", "b")
    with pytest.raises(ValueError):
        reg.gauge("x")  # a name means one kind, forever
    assert reg.labels("x") == ["a", "b"]
    assert reg.labels("nope") == []
    assert reg.kind("x") == "counter" and reg.kind("nope") is None
    reg.counter("m.b")
    reg.counter("m.a")
    assert reg.names() == ["m.a", "m.b", "x"]


def test_snapshot_shapes():
    reg = MetricsRegistry()
    reg.counter("plain").inc(7)
    reg.counter("fan", "lo").inc(1)
    reg.counter("fan", "hi").inc(2)
    reg.histogram("lat").observe(4.0)
    snap = reg.snapshot()
    assert snap["plain"] == 7  # unlabeled family collapses to a scalar
    assert snap["fan"] == {"hi": 2, "lo": 1}  # labeled family nests
    assert snap["lat"]["count"] == 1 and snap["lat"]["p50"] == 4.0
    # numpy payloads survive the JSON rendering
    blob = render_json({"snap": snap, "np": np.int64(3),
                        "arr": np.arange(2.0)})
    back = json.loads(blob)
    assert back["np"] == 3 and back["arr"] == [0.0, 1.0]


def test_legacy_stats_dict_aliases():
    reg = MetricsRegistry()
    stats = LegacyStatsDict({"a": reg.counter("s.a"), "g": reg.gauge("s.g"),
                             "bb": LabeledView(reg, "s.bb")})
    stats["a"] += 2  # read-modify-write lands in the registry
    stats["g"] = 5
    assert stats["a"] == 2 and reg.counter("s.a").value == 2
    assert dict(stats)["g"] == 5 and len(stats) == 3
    with pytest.raises(TypeError):
        stats["bb"] = {}  # labeled families take per-label writes only
    with pytest.raises(TypeError):
        del stats["a"]


def test_labeled_view_dict_face():
    reg = MetricsRegistry()
    bb = LabeledView(reg, "v.bb")
    assert bb.get("dense", 0) == 0 and len(bb) == 0
    with pytest.raises(KeyError):
        bb["dense"]
    bb["dense"] = 3  # write springs the label into existence
    bb["dense"] += 1
    assert bb["dense"] == 4 and set(bb) == {"dense"}
    assert reg.labels("v.bb") == ["dense"]


# --------------------------------------------------------- ops endpoint


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def test_stats_server_contract():
    healthy = [True]
    with StatsServer(lambda: {"n": np.int64(3)},
                     lambda: (healthy[0], "ok" if healthy[0] else "draining"),
                     port=0) as srv:
        base = f"http://127.0.0.1:{srv.port}"
        code, body = _get(base + "/healthz")
        assert (code, body) == (200, b"ok")
        code, body = _get(base + "/stats.json")
        assert code == 200 and json.loads(body) == {"n": 3}
        code, _ = _get(base + "/nope")
        assert code == 404
        healthy[0] = False  # the drain flip: probes must see 503
        code, body = _get(base + "/healthz")
        assert (code, body) == (503, b"draining")
    with pytest.raises(urllib.error.URLError):
        _get(base + "/healthz")  # closed server no longer answers


# ------------------------------------- service/queue registry integration


@pytest.fixture(scope="module")
def burst(g, queries, tmp_path_factory):
    """One queued burst with spill + rank_k + ladder on, shared by the
    integration asserts below; returns (svc, q) after the traffic."""
    spill = str(tmp_path_factory.mktemp("telemetry-spill"))
    svc = svc_for(g, rank_k=2, sweep_dtype="fp32", spill_dir=spill)
    q = svc.queue(deadline_ms=30, max_pending=8)
    tickets = [q.submit(x, priority=(i % 2), deadline_ms=5_000)
               for i, x in enumerate(queries[:6])]
    assert all(t.result(timeout=300) is not None for t in tickets)
    q.close()
    return svc, q


def test_every_emitted_metric_is_in_the_runbook(burst):
    """docs/OPERATIONS.md documents EVERY metric family the registries
    emit — add a metric without documenting it and this fails."""
    svc, q = burst
    with open(RUNBOOK) as f:
        text = f.read()
    emitted = sorted(set(svc.telemetry.names()) | set(q.telemetry.names()))
    assert len(emitted) >= 40  # the layer actually instruments the stack
    missing = [n for n in emitted if n not in text]
    assert not missing, f"undocumented metric families: {missing}"


def test_every_documented_metric_exists(burst):
    """...and the converse: the runbook names no family the code no
    longer emits (docs cannot drift behind a rename)."""
    svc, q = burst
    with open(RUNBOOK) as f:
        text = f.read()
    documented = set(re.findall(
        r"`((?:service|pipeline|queue)\.[a-z0-9_.]+)", text))
    emitted = set(svc.telemetry.names()) | set(q.telemetry.names())
    stale = sorted(documented - emitted)
    assert not stale, f"runbook documents unknown families: {stale}"


def test_service_snapshot_after_traffic(burst):
    svc, q = burst
    snap = svc.telemetry_snapshot()
    assert snap["service.queries"] == 6
    assert snap["service.cache.entries"] == len(svc._cache) > 0
    # per-stage spans recorded for every stage of every swept batch
    stages = snap["pipeline.stage_ms"]
    assert set(stages) == {"assemble", "plan", "sweep", "publish"}
    assert stages["sweep"]["count"] == snap["pipeline.swept"] > 0
    assert stages["sweep"]["p50"] is not None
    # every swept column (cold or warm-started) got a sweep-count
    # observation and an exit reason
    swept_cols = snap["service.cache.cold"] + snap["service.cache.warm"]
    assert snap["service.sweep.iters"]["count"] == swept_cols > 0
    exits = snap["service.exit"]
    assert set(exits) == {"residual", "rank_stable", "max_iter"}
    assert sum(exits.values()) == swept_cols
    assert exits["max_iter"] == 0
    # the fp32 ladder ran on every swept batch; spill writes were timed
    assert snap["service.ladder.bulk_batches"] == snap["pipeline.swept"]
    assert (snap["service.spill.write_ms"]["count"]
            == snap["service.spill.writes"] > 0)
    # legacy dict surface and registry agree (alias, not a copy)
    assert svc.stats["queries"] == 6
    assert dict(svc.stats["backend_batches"]) == snap["service.backend.batches"]


def test_queue_snapshot_after_traffic(burst):
    _svc, q = burst
    snap = q.telemetry_snapshot()
    assert snap["queue.submitted"] == 6
    assert snap["queue.pending"] == 0  # gauge samples live depth
    # each dispatched column got a wait observation
    assert snap["queue.wait_ms"]["count"] >= snap["queue.batches"] > 0
    # both priority classes fanned out their own labels
    cls = snap["queue.class.submitted"]
    assert cls == {"0": 3, "1": 3}
    assert snap["queue.class.latency_ms"]["0"]["count"] == 3
    # snapshot_stats (the legacy renderer) agrees with the registry
    legacy = q.snapshot_stats()
    assert legacy["submitted"] == 6
    assert legacy["classes"][0]["served"] == 3


# ------------------------------------------------------ drain under load


def _stall_dispatcher(svc, q, filler):
    """Under the held sweep lock: feed the dispatcher a filler batch so it
    blocks mid-sweep, leaving the pending set to us."""
    tickets = [q.submit(x) for x in filler]
    deadline = time.perf_counter() + 60
    while q.depth > 0:
        assert time.perf_counter() < deadline, "dispatcher never took filler"
        time.sleep(0.002)
    return tickets


def test_drain_sheds_best_effort_serves_guaranteed(g, queries, tmp_path):
    """drain() under live load: admission stops, pending best-effort
    resolves shed IMMEDIATELY (before the in-flight sweep finishes),
    guaranteed pending is served, the spill is flushed + GC'd."""
    svc = svc_for(g, pipeline_depth=1, v_max=2,
                  spill_dir=str(tmp_path / "spill"))
    svc_for(g, v_max=2).rank(queries[:4])  # compile warmup
    q = svc.queue(deadline_ms=60_000, max_pending=8, shed_priority=1)
    box = {}
    with svc.pipeline._sweep_lock:
        fill = _stall_dispatcher(svc, q, queries[:2])
        a = q.submit(queries[2], priority=0)  # guaranteed pending
        b = q.submit(queries[3], priority=1)  # best-effort pending
        th = threading.Thread(target=lambda: box.update(d=q.drain()))
        th.start()
        deadline = time.perf_counter() + 60
        while not b.done():  # shed happens while the sweep is still held
            assert time.perf_counter() < deadline, "drain never shed"
            time.sleep(0.002)
        assert b.result().status == "shed" and b.result().iters == 0
        assert not a.done()  # guaranteed work is NOT dropped
        with pytest.raises(RuntimeError):
            q.submit(queries[4])  # admission is closed
    th.join(timeout=300)
    assert not th.is_alive()
    d = box["d"]
    assert a.result(timeout=300).status == "cold"
    assert all(t.result(timeout=300).status == "cold" for t in fill)
    assert d["shed"] == 1
    assert d["served"] == 3  # 2 filler + the guaranteed straggler
    assert d["spill_flushed"] is True and d["gc_removed"] >= 0
    assert q.telemetry.counter("queue.drains").value == 1
    # idempotent: a second drain finds nothing new to shed or serve
    d2 = q.drain()
    assert d2["shed"] == 0 and d2["served"] == 3


def test_drain_without_spill_or_traffic(g):
    svc = svc_for(g)
    q = svc.queue(deadline_ms=60_000)
    d = q.drain(flush_spill=True)  # no spill configured: flush is a no-op
    assert d == {"shed": 0, "served": 0,
                 "spill_flushed": False, "gc_removed": 0}
    with pytest.raises(RuntimeError):
        q.submit([1, 2])


# --------------------------------------------- launcher SIGTERM drain


def test_launcher_sigterm_drains_and_exits_zero(tmp_path):
    """The full ops story end-to-end in a subprocess: the launcher serves
    /healthz + /stats.json live during a queued run, SIGTERM mid-burst
    drains (shed best-effort, serve guaranteed, flush spill) and the
    process exits 0 with the drain line on stdout."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["PYTHONUNBUFFERED"] = "1"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"  # tests keep it off
    cmd = [sys.executable, "-m", "repro.launch.serve_rank",
           "--dataset", "synthetic", "--n-nodes", "300", "--n-edges", "2400",
           "--requests", "5000", "--arrival-qps", "100", "--v", "4",
           "--frontend", "queued", "--low-pri-frac", "0.3",
           "--sla-ms", "5000", "--tol", "1e-10",
           "--stats-port", "0", "--spill-dir", str(tmp_path / "spill")]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)
    lines = []

    def _reader():
        for line in proc.stdout:
            lines.append(line)

    th = threading.Thread(target=_reader, daemon=True)
    th.start()
    try:
        # wait for the endpoint banner + the serving marker
        deadline = time.time() + 300
        port = None
        while time.time() < deadline:
            joined = "".join(lines)
            m = re.search(r"stats: GET /healthz /stats\.json on "
                          r"127\.0\.0\.1:(\d+)", joined)
            if m and "serving: queued frontend" in joined:
                port = int(m.group(1))
                break
            if proc.poll() is not None:
                pytest.fail(f"launcher died early:\n{joined}")
            time.sleep(0.1)
        assert port is not None, "".join(lines)
        base = f"http://127.0.0.1:{port}"
        code, body = _get(base + "/healthz")
        assert (code, body) == (200, b"ok")
        code, body = _get(base + "/stats.json")
        assert code == 200
        snap = json.loads(body)
        assert "service" in snap and "queue" in snap
        assert snap["service"]["service.queries"] >= 0
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=300) == 0, "".join(lines)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=60)
    th.join(timeout=30)
    out = "".join(lines)
    m = re.search(r"drain: admission stopped after (\d+) submits, "
                  r"(\d+) best-effort shed, (\d+) served, spill flushed "
                  r"\(gc removed (\d+)\)", out)
    assert m, out
    submits, shed, served = int(m.group(1)), int(m.group(2)), int(m.group(3))
    assert 0 < submits < 5000  # the signal really landed mid-stream
    assert shed + served <= submits + 1  # coalescing can only merge


# ------------------------------------ zero-observation histogram contract


def test_zero_observation_histogram_snapshot_is_null_not_zero():
    """A histogram nobody has observed must report p50/p95/p99 as None —
    a 0.0 would read as "all requests are instant" on a dashboard. Pinned
    because delta/drain histograms commonly sit at zero observations for
    a service's whole lifetime."""
    reg = MetricsRegistry()
    h = reg.histogram("quiet_ms")
    s = h.summary()
    assert s["count"] == 0 and s["sum"] == 0.0
    assert s["p50"] is None and s["p95"] is None and s["p99"] is None
    assert s["min"] is None and s["max"] is None
    snap = reg.snapshot()
    assert snap["quiet_ms"]["p50"] is None
    blob = render_json(snap)
    assert json.loads(blob)["quiet_ms"]["p50"] is None
    assert b'"p50": null' in blob  # JSON null, never 0


def test_zero_observation_histogram_over_stats_endpoint():
    """The same contract end to end: a scraper hitting /stats.json sees
    JSON nulls for an unobserved histogram's percentiles."""
    reg = MetricsRegistry()
    reg.histogram("service.delta.swap_ms")
    with StatsServer(lambda: reg.snapshot(), lambda: (True, "ok"),
                     port=0) as srv:
        code, body = _get(f"http://127.0.0.1:{srv.port}/stats.json")
    assert code == 200
    got = json.loads(body)["service.delta.swap_ms"]
    assert got["count"] == 0
    assert got["p50"] is None and got["p95"] is None and got["p99"] is None
