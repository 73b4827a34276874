"""Trace reduction and useful-bytes arithmetic, on a synthetic trace with
answers worked out by hand and on an excerpt of a trace recorded on a
TPU v5e (``data/trace_v5e_rank.json``)."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce as tr
from bench import work

DATA = Path(__file__).resolve().parent / "data"


def _synthetic():
    # window 0..100 ns; chip 0 runs a loop 10..50 holding two ops, then an
    # op 60..70; chip 1 runs one op 0..30
    return {
        "window": [0.0, 100.0],
        "device": [[0, "%while.1 = (f32[8]) while(...)", 10.0, 40.0],
                   [0, "%fusion.2 = f32[8] fusion(...)", 12.0, 20.0],
                   [0, "%fusion.3 = f32[8] fusion(...)", 35.0, 10.0],
                   [0, "%fusion.2 = f32[8] fusion(...)", 60.0, 10.0],
                   [1, "%copy.1 = f32[8] copy(...)", -5.0, 35.0]],
        "programs": [[0, "jit_f(123)", 10.0, 40.0],
                     [0, "jit_g(456)", 60.0, 10.0],
                     [1, "jit_f(789)", -5.0, 35.0]],
        "host": [["assemble", 0.0, 8.0], ["sweep", 40.0, 55.0],
                 ["publish", 50.0, 58.0]],
    }


def test_union_merges_overlaps_and_skips_empty():
    assert tr.union_ns([(0, 10), (5, 15), (20, 25), (30, 30)]) == 20.0
    assert tr.union_ns([]) == 0.0
    assert tr.union_ns([(0, 100), (10, 20), (30, 40)]) == 100.0


def test_busy_idle_and_gaps_on_synthetic_trace():
    rec = _synthetic()
    # chip 0 busy 40 + 10 = 50 ns; chip 1 clipped to 0..30 = 30 ns
    assert tr.busy_s(rec) == pytest.approx(40e-9)
    assert tr.window_s(rec) == pytest.approx(100e-9)
    assert tr.idle_share(rec) == pytest.approx(0.6)
    assert tr.idle_gaps(rec, 0) == [(0.0, 10.0), (50.0, 60.0), (70.0, 100.0)]


def test_leaf_ops_drop_the_loop_around_its_body():
    names = sorted(n for c, n, _s, _d in tr.leaf_ops(_synthetic()) if c == 0)
    assert [tr._short(n) for n in names] == ["%fusion.2", "%fusion.2",
                                              "%fusion.3"]


def test_top_ops_name_program_and_op():
    top = dict(tr.top_ops(_synthetic()))
    assert top == pytest.approx({"jit_f/%fusion.2": 20e-9,
                                 "jit_g/%fusion.2": 10e-9,
                                 "jit_f/%fusion.3": 10e-9,
                                 "jit_f/%copy.1": 30e-9})


def test_idle_by_host_splits_gaps_by_span():
    got = dict(tr.idle_by_host(_synthetic()))
    # gaps 0..10, 50..60, 70..100: assemble 0..8, sweep 50..55,
    # publish 55..58 (the part sweep left), the rest no span
    assert got == pytest.approx({"assemble": 8e-9, "sweep": 5e-9,
                                 "publish": 3e-9, "no host span": 34e-9})


def test_recorded_chip_trace_excerpt():
    rec = json.loads((DATA / "trace_v5e_rank.json").read_text())
    busy, window = tr.busy_s(rec), tr.window_s(rec)
    assert window == pytest.approx(0.5)
    # an independent union: rasterise at 1 us
    lo, hi = rec["window"]
    grid = np.zeros(int((hi - lo) / 1e3) + 1, bool)
    for _c, _n, s, d in rec["device"]:
        a = int(max(s - lo, 0) // 1e3)
        b = int(min(s + d - lo, hi - lo) // 1e3)
        grid[a:b] = True
    assert busy == pytest.approx(grid.sum() * 1e-6, rel=2e-3)
    gaps = tr.idle_gaps(rec)
    assert sum(b - a for a, b in gaps) * 1e-9 == pytest.approx(window - busy)
    top = tr.top_ops(rec)
    assert top[0][0] == "jit__partial_a/%fusion.2"  # the engine's scatter
    assert sum(t for _n, t in top) <= busy + 1e-12
    idle = dict(tr.idle_by_host(rec))
    assert sum(idle.values()) == pytest.approx(window - busy)
    assert max(idle, key=idle.get) == "engine.build"


def test_sweep_bytes_count_endpoints_and_vectors():
    # per pass: 8 bytes of endpoints per edge, three vectors of n
    assert work.sweep_bytes(10, 100, 8) == 2 * (8 * 100 + 3 * 10 * 8)
    assert work.sweep_bytes(0, 0, 8) == 0
    # a query pays its sweeps plus one certificate sweep
    assert work.query_bytes(10, 100, 4, 8) == 5 * work.sweep_bytes(10, 100, 8)
    assert work.graph_bytes(225441, 2469024, 9, 8) == \
        9 * work.sweep_bytes(225441, 2469024, 8)
    # lower precision moves fewer bytes for the same graph
    assert work.sweep_bytes(1000, 10000, 4) < work.sweep_bytes(1000, 10000, 8)
