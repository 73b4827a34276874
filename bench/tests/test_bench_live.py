"""The live-crawl configuration at a size a test can hold: a sound run
comes out correct; the lower-precision control, an answer stamped older
than the version acknowledged before its submit, an answer computed on
another version than its stamp, and a refused submit each come out not
correct. The limits and the control are the committed ones; only the
graph, the traffic and the crawl's pace are small. Also: the crawl feed's
roll times are the same for every seed, and its links are drawn from it."""
import dataclasses
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import graphs, harness
from bench.drivers import serve_live

ROOT = Path(__file__).resolve().parents[2]
GRAPH = {"pages": 3000, "links": 24000, "dangling_pct": 60.0,
         "alpha_in": 2.1, "alpha_out": 2.7, "seed": 5, "back_button": False}
SEED = 2**31 + 99


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark whose live cell is small: three rolls of
    40 links, one with a page, within a 1.6 s window."""
    import jax
    jax.config.update("jax_enable_x64", True)
    r = tmp_path_factory.mktemp("bench_live")
    shutil.copytree(ROOT / "bench", r / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = json.loads((ROOT / "bench/configs/kleinberg-stanford-live.json")
                     .read_text())
    cfg.update(graph=GRAPH, query={"roots": 20, "in_cap": 8, "out_cap": 8},
               service=dict(cfg["service"], out_cap=8, in_cap=8),
               crawl=dict(cfg["crawl"], first_roll_s=0.3, roll_every_s=0.4,
                          window_rolls=3, links_added=40, links_removed=4,
                          page_rolls=[0, 2]))
    (r / "bench/configs/small-live.json").write_text(json.dumps(cfg))
    (r / "bench/traffic/small-open.json").write_text(json.dumps(
        {"loop": "open", "rate_qps": 30.0, "popularity_beta": 1.0,
         "warmup_widths": [1, 8], "warmup_rounds": 1,
         "warmup_cover_widths": [1, 2], "warmup_cover": 2}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "small-live", "source": "test",
                         "file": "bench/configs/small-live.json",
                         "reduced": [], "why": "t"}]
    bench["workloads"] = [{"name": "small-live.open", "config": "small-live",
                           "traffic": "small-open", "chips": 1, "why": "t"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (r / "BENCHMARK.json").write_text(json.dumps(bench))
    return r


def _run(root, **kw):
    return harness.run_cell(root, "small-live.open", SEED, 1.6,
                            kw.pop("trace", False), log=lambda _m: None,
                            **kw)


def test_live_cell_sound_run_is_correct(root):
    out = _run(root, trace=True)
    assert out["correct"], out["checks"]
    # 48 are due; the schedule's last is due 2.4 ms before the window's
    # end, and a loaded CPU can wake the sender past it
    assert out["attempted"] in (47, 48) and out["failed"] == 0
    for k in ("stale_serves", "refused", "rolls_failed"):
        assert out["checks"][k] == {"value": 0, "limit": 0}
    # the roll's spans, read from a CPU trace: the device metric is not
    assert out["metrics"]["roll.ms.live"]["value"] > 0
    assert out["metrics"]["delta.apply_ms.live"]["value"] > 0
    assert "roll.idle_ms.live" not in out["metrics"]
    # the overload cell's layers read the same record
    for k in ("assemble.ms", "plan.ms", "queue.wait_ms", "serve.p95_ms"):
        assert out["metrics"][k]["value"] > 0


def test_live_cell_control_is_not_correct(root):
    out = _run(root, control=True)
    assert not out["correct"], out["checks"]


def _stamp_version_zero(monkeypatch):
    """Every answer claims version 0, whatever it was computed on."""
    from repro.serve.pipeline import ServePipeline
    real = ServePipeline.publish

    def publish(self, asm):
        return [None if r is None else dataclasses.replace(
            r, graph_version=0) for r in real(self, asm)]

    monkeypatch.setattr(ServePipeline, "publish", publish)


def _rank_on_first_version(monkeypatch):
    """Batches are ranked on the service's first graph version and
    stamped with the live one."""
    from repro.serve import rank_service
    real = rank_service.RankService.apply_edge_delta

    def apply_edge_delta(self, *a, **k):
        first = self.__dict__.setdefault("_first", self._live)
        self._live = self.__dict__.get("_true", first)  # roll the true chain
        ack = real(self, *a, **k)
        self._true = self._live
        self._live = dataclasses.replace(first, version=self._live.version)
        return ack

    monkeypatch.setattr(rank_service.RankService, "apply_edge_delta",
                        apply_edge_delta)


def _refuse_every_fifth(monkeypatch):
    from repro.serve.queue import RankQueue
    real = RankQueue.submit
    calls = []

    def submit(self, *a, **k):
        calls.append(1)
        if len(calls) % 5 == 0:
            raise RuntimeError("queue is closed")
        return real(self, *a, **k)

    monkeypatch.setattr(RankQueue, "submit", submit)


@pytest.mark.parametrize("fault", [_stamp_version_zero,
                                   _rank_on_first_version,
                                   _refuse_every_fifth],
                         ids=["stamped_older", "wrong_version", "refused"])
def test_live_cell_fault_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    out = _run(root)
    assert not out["correct"], out["checks"]


def test_program_without_versions_stops_at_setup(root, monkeypatch):
    from repro.serve import rank_service
    fields = dict(rank_service.QueryResult.__dataclass_fields__)
    fields.pop("graph_version")
    monkeypatch.setattr(rank_service.QueryResult, "__dataclass_fields__",
                        fields)
    with pytest.raises(RuntimeError, match="graph_version"):
        _run(root)


# ------------------------------------------------------------ the feed


@pytest.fixture(scope="module")
def small_graph():
    return graphs.build(GRAPH)


def _feed(small_graph, seed):
    cfg = json.loads((ROOT / "bench/configs/kleinberg-stanford-live.json")
                     .read_text())
    n, src, dst = small_graph
    return serve_live.Feed(n, src, dst, cfg["crawl"], seed)


def test_feed_times_are_the_same_for_every_seed(small_graph):
    a, b = _feed(small_graph, 1), _feed(small_graph, 2**31 + 7)
    assert a.times(51) == b.times(51) == [5.0, 15.0, 25.0, 35.0, 45.0]
    assert [d["pages"] for d in a.deltas] == [1, 0, 0, 0, 1, 0]
    assert [d["pages"] for d in a.deltas] == [d["pages"] for d in b.deltas]


def test_feed_links_are_drawn_from_the_seed(small_graph):
    a, again, b = (_feed(small_graph, s) for s in (3, 3, 4))
    assert a.deltas == again.deltas
    assert a.deltas != b.deltas
    n, src, dst = small_graph
    have = set(zip(src.tolist(), dst.tolist()))
    for k, (v, nv, s, d) in zip(range(len(a.deltas)), a.versions()):
        delta = a.deltas[k]
        assert len(delta["adds"]) == 9 and len(delta["removes"]) == 1
        assert not set(delta["adds"]) & have
        assert set(delta["removes"]) <= have
        assert all(s_ != d_ for s_, d_ in delta["adds"])
        if delta["pages"]:  # the page's one in-link and one out-link
            assert [p for p in delta["adds"] if nv in p] == \
                [(delta["adds"][0][0], nv), (nv, delta["adds"][1][1])]
        have = (have - set(delta["removes"])) | set(delta["adds"])
    *_, (v, nv, s, d) = a.versions()
    assert v == len(a.deltas) and nv == n + 2
    assert set(zip(s.tolist(), d.tolist())) == have
    assert np.all(np.diff(s * (1 << 32) + d) > 0)
