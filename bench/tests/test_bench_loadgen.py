"""The traffic generator is a function of the seed, latency is taken from
when each request was due, and the benchmark's own clock reads when each
request was answered."""
import json
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from bench import loadgen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))
QUERY_MIXES = [m for m in MIXES
               if "popularity_beta" in json.loads((TRAFFIC / f"{m}.json")
                                                  .read_text())]
BIG_SEED = 2**31 + 12345


@pytest.fixture(scope="module")
def indeg():
    rng = np.random.default_rng(0)
    return rng.zipf(2.1, size=5000) - 1


@pytest.mark.parametrize("mix", QUERY_MIXES)
def test_each_query_mix_is_determined_by_its_seed(mix, indeg):
    m = json.loads((TRAFFIC / f"{mix}.json").read_text())

    def stream(seed, chunks):
        r = loadgen.Requests(m, indeg, 20, seed)
        out = []
        for k in chunks:
            out += r.take(k)
        return out

    a = stream(BIG_SEED, [100])
    b = stream(BIG_SEED, [1, 7, 30, 62])  # asked for in other pieces
    assert len(a) == len(b) == 100
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    c = stream(BIG_SEED + 1, [100])
    assert not any(np.array_equal(x, y) for x, y in zip(a, c))
    for roots in a:
        assert len(roots) == 20 and len(np.unique(roots)) == 20
        assert roots.min() >= 0 and roots.max() < len(indeg)
    assert len({tuple(r) for r in a}) == len(a)  # never repeated


def test_schedule_is_determined_by_seed_and_has_a_fixed_count():
    a = loadgen.poisson_schedule(loadgen.rng_for(BIG_SEED, 5), 6.4, 51.0)
    b = loadgen.poisson_schedule(loadgen.rng_for(BIG_SEED, 5), 6.4, 51.0)
    c = loadgen.poisson_schedule(loadgen.rng_for(7, 5), 6.4, 51.0)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == len(c) == round(6.4 * 51.0)
    assert (np.diff(a) >= 0).all() and a[0] >= 0 and a[-1] < 51.0


def test_streams_of_one_seed_are_independent():
    draws = {s: loadgen.rng_for(BIG_SEED, s).random(4)
             for s in (loadgen.WINDOW, loadgen.WARMUP, loadgen.RELABEL,
                       loadgen.SCHEDULE)}
    assert len({tuple(v) for v in draws.values()}) == len(draws)
    neg = loadgen.rng_for(-3, 0).random(4)
    assert not np.array_equal(neg, loadgen.rng_for(3, 0).random(4))


def test_popularity_prefers_linked_pages():
    p = loadgen.popularity(np.array([0, 1, 9]), 1.0)
    assert p == pytest.approx(np.array([1.0, 2.0, 10.0]) / 13.0)
    assert loadgen.popularity(np.array([0, 5]), 0.0) == pytest.approx([.5, .5])
    rng = np.random.default_rng(1)
    sets = loadgen.root_sets(rng, loadgen.popularity(
        np.array([0] * 90 + [1000] * 10), 1.0), 200, 5)
    hot = np.mean([np.isin(s, np.arange(90, 100)).mean() for s in sets])
    assert hot > 0.8


def test_latency_runs_from_the_due_time():
    due = np.array([1.0, 2.0, 3.0])
    lat = loadgen.latencies_ms(due, [1.5, 2.25, None])
    assert lat[:2] == pytest.approx([500.0, 250.0])
    assert lat[2] == np.inf  # never answered: infinitely late
    # a send that was late still counts from the due time
    assert loadgen.lateness_ms(due, [1.0, 2.01, 3.5]) == \
        pytest.approx([0.0, 10.0, 500.0])
    assert loadgen.lateness_ms(due, [0.9, 2.0, 3.0])[0] == 0.0


def test_percentile_counts_failed_requests_as_late():
    ok = np.arange(1.0, 101.0)
    assert loadgen.percentile(ok, 50) == pytest.approx(np.percentile(ok, 50))
    assert loadgen.percentile(ok, 95) == pytest.approx(np.percentile(ok, 95))
    bad = np.concatenate([ok[:90], [np.inf] * 10])
    assert loadgen.percentile(bad, 95) == np.inf
    assert loadgen.percentile(bad, 50) == pytest.approx(50.5)
    assert np.isnan(loadgen.percentile([], 50))


class _Ticket:
    """A stand-in for the queue's ticket, answered by ``answer()``."""

    def __init__(self):
        self._done = threading.Event()
        self.answered_at = None

    def done(self):
        return self._done.is_set()

    def result(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError
        return "answer"

    def answer(self):
        self.answered_at = time.perf_counter()
        self._done.set()


def test_resolve_clock_stamps_after_each_answer_in_any_order():
    clock = loadgen.ResolveClock()
    tickets = [_Ticket() for _ in range(4)]
    for i, t in enumerate(tickets):
        clock.watch(i, t)
    # answered out of order: the oldest last
    for i in (2, 1, 3, 0):
        time.sleep(0.02)
        tickets[i].answer()
    stamps = clock.close(time.perf_counter() + 5.0)
    assert sorted(stamps) == [0, 1, 2, 3]
    for i, t in enumerate(tickets):
        lag = stamps[i] - t.answered_at
        assert 0.0 <= lag < 0.015, (i, lag)


def test_resolve_clock_leaves_unanswered_tickets_unstamped():
    clock = loadgen.ResolveClock()
    done, never = _Ticket(), _Ticket()
    clock.watch("never", never)
    clock.watch("done", done)
    done.answer()
    t = time.perf_counter()
    stamps = clock.close(t + 0.1)
    assert "never" not in stamps and "done" in stamps
    assert time.perf_counter() - t < 1.0
