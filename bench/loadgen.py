"""Traffic from a mix's parameters and a seed, and the clock it is timed by.

One generator reads every mix file under ``bench/traffic/``:

- ``popularity_beta``: root pages are drawn without replacement with
  probability proportional to (in-degree + 1) ** beta, since text-match
  results favour pages that others link to;
- ``rate_qps``: requests are sent on a Poisson schedule at this rate
  (``loop`` is ``"open"``). Root sets are never repeated.

Every stream is a function of (seed, stream number) alone, so the same
seed gives the same requests in the same order.
"""
from __future__ import annotations

import collections
import math
import threading
import time

import numpy as np

# stream numbers: one independent random stream per use of the seed
WINDOW, WARMUP, RELABEL, SCHEDULE = 0, 1, 2, 5


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """The random stream ``stream`` of a run's ``--seed``."""
    seed = int(seed)
    return np.random.default_rng([abs(seed), int(seed < 0), stream])


def popularity(indeg: np.ndarray, beta: float) -> np.ndarray:
    """Draw probabilities proportional to (in-degree + 1) ** beta."""
    w = (np.asarray(indeg, np.float64) + 1.0) ** float(beta)
    return w / w.sum()


def root_sets(rng, p: np.ndarray, count: int, size: int) -> list:
    """``count`` root sets of ``size`` distinct pages each, drawn by
    successive sampling without replacement from probabilities ``p``
    (independent draws, each page kept at its first draw)."""
    cdf = np.cumsum(p)
    cdf /= cdf[-1]
    out = []
    for _ in range(count):
        picked = np.zeros(0, np.int64)
        while len(picked) < size:
            draw = np.searchsorted(cdf, rng.random(2 * size), side="right")
            both = np.concatenate([picked, draw])
            _, first = np.unique(both, return_index=True)
            picked = both[np.sort(first)][:size]
        out.append(picked)
    return out


def poisson_schedule(rng, rate: float, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of a Poisson stream at
    ``rate`` over ``seconds``, conditioned on its expected count, so every
    seed sends the same number of requests."""
    count = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, count))


class Requests:
    """The request stream of one run: ``take(k)`` gives the next ``k`` root
    sets, the same ones for the same seed whatever ``k`` is asked for."""

    CHUNK = 64

    def __init__(self, mix: dict, indeg: np.ndarray, roots: int, seed: int,
                 stream: int = WINDOW):
        self.roots = int(roots)
        self.p = popularity(indeg, mix.get("popularity_beta", 0.0))
        self.rng = rng_for(seed, stream)
        self._buf: list = []

    def take(self, k: int) -> list:
        while len(self._buf) < k:
            self._buf += root_sets(self.rng, self.p, self.CHUNK, self.roots)
        out, self._buf = self._buf[:k], self._buf[k:]
        return out


def latencies_ms(due_s, resolved_s) -> np.ndarray:
    """Latency of each request from when it was due, in ms; a request
    that never resolved (None or NaN) reads as infinitely late."""
    due = np.asarray(due_s, np.float64)
    res = np.array([np.inf if r is None else r for r in resolved_s],
                   np.float64)
    res[np.isnan(res)] = np.inf
    return (res - due) * 1e3


def lateness_ms(due_s, sent_s) -> np.ndarray:
    """How late the generator sent each request, in ms (never negative:
    the generator does not send early)."""
    return np.maximum(np.asarray(sent_s, np.float64)
                      - np.asarray(due_s, np.float64), 0.0) * 1e3


def percentile(values, q: float) -> float:
    """The ``q``-th percentile of all values, infinite ones included
    (numpy's linear interpolation)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return float("nan")
    pos = (v.size - 1) * q / 100.0
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if v[hi] == np.inf:
        return float("inf")
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


class ResolveClock:
    """When each request was answered, read on the benchmark's own clock.

    A thread waits on the oldest open ticket (anything with ``done()`` and
    ``result(timeout)``); each time it wakes, at the latest every
    ``POLL_S``, it reads ``time.perf_counter()`` once it has seen which
    tickets are done, and stamps them all with it. A stamp is thus never
    before the answer, and later by at most one wake-up."""

    POLL_S = 0.005

    def __init__(self):
        self.at: dict = {}
        self._open = collections.deque()
        self._lock = threading.Lock()
        self._closing = False
        self._deadline = math.inf
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-resolve-clock")
        self._thread.start()

    def watch(self, key, ticket):
        with self._lock:
            self._open.append((key, ticket))

    def _run(self):
        while True:
            with self._lock:
                head = self._open[0][1] if self._open else None
                if (self._closing and head is None) \
                        or time.perf_counter() >= self._deadline:
                    return
            if head is None:
                time.sleep(self.POLL_S)
                continue
            try:
                head.result(timeout=self.POLL_S)
            except Exception:  # noqa: BLE001 — pending, or answered badly
                pass
            with self._lock:
                done = [kt for kt in self._open if kt[1].done()]
                now = time.perf_counter()
                for key, _t in done:
                    self.at[key] = now
                self._open = collections.deque(
                    kt for kt in self._open if kt[0] not in self.at)

    def close(self, deadline: float) -> dict:
        """Wait until every watched ticket is stamped, or until the
        ``perf_counter`` instant ``deadline``; the stamps by key."""
        with self._lock:
            self._closing, self._deadline = True, deadline
        self._thread.join()
        return self.at
