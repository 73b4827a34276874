"""Minimal stand-in for ``hypothesis`` on bare environments.

Installed into ``sys.modules`` by conftest.py ONLY when the real package is
absent, so the property-test modules collect and still exercise their
properties. The fallback draws a fixed number of deterministic
pseudo-random examples per test (seeded rng — reproducible across runs);
there is no shrinking and no database. Implements exactly the surface this
repo's tests use: ``given``, ``settings``, ``assume``, and the
``strategies`` ``integers`` / ``floats`` / ``lists`` / ``tuples``.
"""
from __future__ import annotations

import sys
import types

import numpy as np

FALLBACK_MAX_EXAMPLES = 25  # cap: smoke-level coverage, CI-fast


class _Strategy:
    def __init__(self, draw):
        self.draw = draw  # draw(rng) -> example value


def integers(min_value, max_value):
    return _Strategy(lambda rng: int(rng.integers(min_value, max_value + 1)))


def floats(min_value, max_value):
    return _Strategy(lambda rng: float(rng.uniform(min_value, max_value)))


def tuples(*strats):
    return _Strategy(lambda rng: tuple(s.draw(rng) for s in strats))


def lists(elements, min_size=0, max_size=None):
    hi = min_size + 10 if max_size is None else max_size

    def draw(rng):
        size = int(rng.integers(min_size, hi + 1))
        return [elements.draw(rng) for _ in range(size)]

    return _Strategy(draw)


class _Rejected(Exception):
    """An ``assume`` failed: the example is discarded, not failed."""


def assume(condition):
    if not condition:
        raise _Rejected
    return True


def settings(max_examples=100, deadline=None, **_kw):
    def deco(fn):
        fn._fallback_max_examples = min(max_examples, FALLBACK_MAX_EXAMPLES)
        return fn

    return deco


def given(*strats, **kw_strats):
    def deco(fn):
        # NB: no functools.wraps — pytest would read the wrapped signature
        # and treat the drawn parameters as missing fixtures.
        def runner():
            n = getattr(fn, "_fallback_max_examples", FALLBACK_MAX_EXAMPLES)
            rng = np.random.default_rng(0)
            for _ in range(n):
                drawn = tuple(s.draw(rng) for s in strats)
                drawn_kw = {k: s.draw(rng) for k, s in kw_strats.items()}
                try:
                    fn(*drawn, **drawn_kw)
                except _Rejected:
                    continue

        runner.__name__ = fn.__name__
        runner.__qualname__ = getattr(fn, "__qualname__", fn.__name__)
        runner.__module__ = fn.__module__
        runner.__doc__ = fn.__doc__
        runner.is_hypothesis_test = False  # fallback, not the real thing
        return runner

    return deco


def install():
    """Register the stub as ``hypothesis`` in sys.modules."""
    mod = types.ModuleType("hypothesis")
    mod.given = given
    mod.settings = settings
    mod.assume = assume
    mod.HealthCheck = types.SimpleNamespace(too_slow=None, data_too_large=None)
    st = types.ModuleType("hypothesis.strategies")
    st.integers = integers
    st.floats = floats
    st.lists = lists
    st.tuples = tuples
    mod.strategies = st
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = st
