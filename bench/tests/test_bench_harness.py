"""BENCHMARK.json names only what exists, in the shape the harness reads,
and the command refuses to run where it cannot measure a chip."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [c["name"] for c in BENCH["workloads"]]


def test_names_units_and_bounds():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    for n in names + [c["traffic"] for c in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its 12 hours
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_cell_reports_what_its_metrics_move():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for cell in CELLS:
        mine = [n for n, m in e2e.items() if reports(m, cell)]
        assert "setup_s" in mine and len(mine) >= 2, cell
        assert any(reports(m, cell) for m in BENCH["per_layer"]), cell
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in CELLS and reports(e2e[m["moves"]], cell)
        if "_roofline" in m["name"]:
            assert m["unit"] == "%"


def test_every_named_piece_has_its_file():
    for c in BENCH["configs"]:
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (ROOT / "bench" / "drivers" / f"{cfg['entry']}.py").is_file()
        assert set(cfg["reduced"]) == set(c["reduced"])
    for cell in BENCH["workloads"]:
        assert cell["chips"] in (1, 4)
        harness.load_mix(ROOT, cell["traffic"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(harness.load_reader(ROOT, m["name"]).read)


def test_cell_metrics_pick_the_group_by_trace():
    cell = "kleinberg-stanford.overload"
    plain = [m["name"] for m in harness.cell_metrics(BENCH, cell, False)]
    traced = [m["name"] for m in harness.cell_metrics(BENCH, cell, True)]
    assert set(plain) == {"qps", "setup_s"}
    assert "device.idle" in traced and "device.idle.rank" not in traced


def test_qps_counts_every_answer_over_the_whole_window():
    qps = harness.load_reader(ROOT, "qps").read
    assert qps({"answered": 240, "window_s": 60.0}) == 4.0
    assert qps({"answered": 0, "window_s": 60.0}) is None


def _run(args, cwd, **env):
    e = {k: v for k, v in os.environ.items()
         if k != "REPRO_PALLAS_INTERPRET"}
    e.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=e, capture_output=True, text=True, timeout=300)


ARGS = ["--workload", "kleinberg-stanford.overload", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def test_refuses_without_a_tpu():
    p = _run(ARGS, ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_refuses_under_pallas_interpret():
    p = _run(ARGS, ROOT, REPRO_PALLAS_INTERPRET="1")
    assert p.returncode != 0 and "REPRO_PALLAS_INTERPRET" in p.stderr
    assert "{" not in p.stdout


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(ARGS, tmp_path)
    assert p.returncode != 0 and "{" not in p.stdout
