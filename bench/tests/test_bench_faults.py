"""A whole run of each configuration, at a size a test can hold, past the
harness's look for a chip: sound runs come out correct, and the
lower-precision control and each fault the cell can have come out not
correct. The configurations' limits and controls are the committed ones;
only the graph and the traffic are small. On one chip there is no
exchange between chips to leave out.

A second part adds a configuration, a traffic mix and a metric as new
files to a copy of the benchmark and runs them with no other edit."""
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
GRAPH = {"pages": 3000, "links": 24000, "dangling_pct": 60.0,
         "alpha_in": 2.1, "alpha_out": 2.7, "seed": 5}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark whose cells are small."""
    import jax
    jax.config.update("jax_enable_x64", True)
    r = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(ROOT / "bench", r / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    query = json.loads((ROOT / "bench/configs/kleinberg-stanford.json")
                       .read_text())
    query.update(graph=dict(GRAPH, back_button=False),
                 query={"roots": 20, "in_cap": 8, "out_cap": 8},
                 service=dict(query["service"], out_cap=8, in_cap=8))
    whole = json.loads((ROOT / "bench/configs/backbutton-stanford.json")
                       .read_text())
    whole.update(graph=dict(GRAPH, back_button=True))
    (r / "bench/configs/small-query.json").write_text(json.dumps(query))
    (r / "bench/configs/small-whole.json").write_text(json.dumps(whole))
    (r / "bench/traffic/small-open.json").write_text(json.dumps(
        {"loop": "open", "rate_qps": 30.0, "popularity_beta": 1.0,
         "warmup_widths": [1, 8], "warmup_rounds": 1,
         "warmup_cover_widths": [1, 2], "warmup_cover": 2}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [
        {"name": "small-query", "source": "test",
         "file": "bench/configs/small-query.json", "reduced": [], "why": "t"},
        {"name": "small-whole", "source": "test",
         "file": "bench/configs/small-whole.json", "reduced": [], "why": "t"}]
    bench["workloads"] = [
        {"name": "small-query.open", "config": "small-query",
         "traffic": "small-open", "chips": 1, "why": "t"},
        {"name": "small-whole.rank", "config": "small-whole",
         "traffic": "rank", "chips": 1, "why": "t"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (r / "BENCHMARK.json").write_text(json.dumps(bench))
    return r


def _run(root, cell, **kw):
    from bench import harness
    return harness.run_cell(root, cell, 2**31 + 99, kw.pop("seconds", 1.0),
                            kw.pop("trace", False), log=lambda _m: None,
                            **kw)


def _swap_first_and_last(x):
    x = np.array(x)
    x[[0, -1]] = x[[-1, 0]]
    return x


# --------------------------------------------------------- query-time cell


def _patch_sweep(monkeypatch, fault):
    from repro.serve.backends import DenseSweepBackend
    real = DenseSweepBackend.sweep

    def sweep(self, plan, b):
        h, a, conv, res = real(self, plan, b)
        return fault(b, h.copy(), a.copy(), conv, res)

    monkeypatch.setattr(DenseSweepBackend, "sweep", sweep)


def _unchanged(b, h, a, conv, res):
    return np.asarray(b.h0), np.asarray(b.h0), conv, res


def _half_batch(b, h, a, conv, res):
    half = h.shape[1] // 2
    h[:, :half] = 0.0
    a[:, :half] = 0.0
    return h, a, conv, res


def _altered(b, h, a, conv, res):
    n = int(np.asarray(b.mask)[:, 0].sum())
    a[:n, 0] = _swap_first_and_last(a[:n, 0])
    return h, a, conv, res


def test_query_cell_sound_run_is_correct(root):
    out = _run(root, "small-query.open")
    assert out["correct"], out["checks"]
    assert out["attempted"] == 30 and out["failed"] == 0
    assert set(out["metrics"]) == {"qps", "setup_s"}
    assert out["device"]["platform"] == "cpu"


def test_query_cell_control_is_not_correct(root):
    out = _run(root, "small-query.open", control=True)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered],
                         ids=["state_unchanged", "half_batch", "altered"])
def test_query_cell_fault_is_not_correct(root, monkeypatch, fault):
    _patch_sweep(monkeypatch, fault)
    out = _run(root, "small-query.open")
    assert not out["correct"], out["checks"]


# ------------------------------------------------------- whole-graph cell


def test_whole_graph_cell_sound_run_is_correct(root):
    out = _run(root, "small-whole.rank", seconds=0.3)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"rank_s", "setup_s"}


def test_whole_graph_cell_control_is_not_correct(root):
    out = _run(root, "small-whole.rank", seconds=0.3, control=True)
    assert not out["correct"], out["checks"]


def _engine_fault(monkeypatch, name):
    from repro.core import engine
    E = engine.RankingEngine
    if name == "state_unchanged":
        monkeypatch.setattr(E, "_sweep",
                            lambda self, h, *a, **k: (h, h, 0))
    elif name == "half_batch":
        real_init = E.__init__

        def init(self, *a, **k):
            real_init(self, *a, **k)
            self.shards = self.shards[: len(self.shards) // 2]

        monkeypatch.setattr(E, "__init__", init)
    else:
        real_run = E.run

        def run(self, *a, **k):
            res = real_run(self, *a, **k)
            res.authority = _swap_first_and_last(res.authority)
            return res

        monkeypatch.setattr(E, "run", run)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered"])
def test_whole_graph_cell_fault_is_not_correct(root, monkeypatch, fault):
    _engine_fault(monkeypatch, fault)
    out = _run(root, "small-whole.rank", seconds=0.3)
    assert not out["correct"], out["checks"]


# ------------------------------------------------- adding cells by files


def test_new_config_mix_and_metric_need_no_edit(root, tmp_path):
    r = tmp_path / "extended"
    shutil.copytree(root, r)
    (r / "bench/configs/small-whole-2.json").write_text(
        (r / "bench/configs/small-whole.json").read_text())
    (r / "bench/traffic/rank-2.json").write_text(
        (r / "bench/traffic/rank.json").read_text())
    (r / "bench/metrics/jobs.count.py").write_text(
        "def read(run):\n    return len(run.get('jobs', [])) or None\n")
    bench = json.loads((r / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "small-whole-2", "source": "test",
                             "file": "bench/configs/small-whole-2.json",
                             "reduced": [], "why": "t"})
    bench["workloads"].append({"name": "small-whole-2.rank-2",
                               "config": "small-whole-2",
                               "traffic": "rank-2", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "jobs.count", "unit": "jobs",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine", "moves": "rank_s",
                               "workloads": ["small-whole-2.rank-2"]})
    (r / "BENCHMARK.json").write_text(json.dumps(bench))
    out = _run(r, "small-whole-2.rank-2", seconds=0.3, trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["jobs.count"]["value"] >= 1
    # a CPU trace has no TPU plane: no device metric is made up
    assert "device.idle.rank" not in out["metrics"]
