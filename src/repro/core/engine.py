"""Production ranking engine: sharded power iteration with checkpointing,
bounded-staleness straggler tolerance, and elastic re-sharding.

The engine partitions edges into ``n_shards`` virtual shards (on hardware,
one per host/slice; here executed sequentially — the combine semantics are
identical). Per sweep each shard contributes a partial authority/hub
product, summed per page with no scatter: each shard's edges are sorted
once per build by destination and by source (``_order``), and each pass
is a gather and a scan that restarts at each page's first edge
(``_segment_sum``). The combine is a sum, so the engine tolerates:

* **Stragglers**: a shard that misses the deadline reuses its previous
  partial (bounded staleness ``stale_limit``). Power iteration is a
  self-correcting fixed point — stale partials perturb the iterate but not
  the limit; tests verify convergence to the exact vectors.
* **Failures/preemption**: state (h, k, staleness, shard partials) is
  checkpointed via repro.checkpoint; ``resume`` continues mid-iteration.
* **Elastic re-sharding**: edges can be repartitioned to a different shard
  count at restart; the fixed point is shard-count invariant.
"""
from __future__ import annotations

import dataclasses
import itertools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import checkpoint as ckpt_mod
from ..graph.partition import partition_edges
from ..graph.structure import Graph
from ..serve.telemetry import span
from .weights import accel_weights


_LANES = 128  # the sorted edges lie in the columns of a (rows, 128) array


@partial(jax.jit, static_argnames=("n",))
def _order(key, other, w, n):
    """One shard's edges in ``key`` order, as ``_segment_sum`` takes them.

    Column c of each (rows, 128) array holds the sorted edges
    ``[c * rows, (c + 1) * rows)``: each edge's other endpoint, its weight
    and whether it opens its key's run; the slots past the last edge hold
    weight 0 and extend the last run. Per page: the flat index of its last
    edge in that layout, and whether it has an edge here.
    """
    e = key.shape[0]
    sk, perm = jax.lax.sort((key, jnp.arange(e, dtype=jnp.int32)),
                            num_keys=1)
    rows = -(-e // (8 * _LANES)) * 8
    pad = rows * _LANES - e

    def columns(x):
        return jnp.pad(x, (0, pad)).reshape(_LANES, rows).T

    start = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    # one past each page's last edge, 0 where it has none: an integer max,
    # exact in any order
    end = jnp.zeros((n,), jnp.int32).at[sk].max(
        jnp.arange(1, e + 1, dtype=jnp.int32), indices_are_sorted=True)
    last = jnp.maximum(end - 1, 0)
    return (columns(jnp.take(other, perm)), columns(jnp.take(w, perm)),
            columns(start), last % rows * _LANES + last // rows, end > 0)


def _restart(a, b):
    """Combine of an inclusive scan that restarts at each flagged element."""
    fa, va = a
    fb, vb = b
    return fa | fb, jnp.where(fb, vb, va + vb)


@jax.jit
def _segment_sum(v, other, w, start, last, has):
    """``out[j] = sum(v[other[e]] * w[e] for the edges e keyed j)`` over one
    shard's edges in key order (``_order``), with no scatter: a scan that
    restarts at each key's first edge adds each page's products in the
    vector's dtype, and each page reads its last edge's running sum."""
    with jax.named_scope("segsum.gather"):
        x = jnp.take(v, other) * w
    with jax.named_scope("segsum.scan"):
        # down each column, then a run that crosses into a column takes
        # the sum carried out of the columns before it
        opened, run = jax.lax.associative_scan(_restart, (start, x), axis=0)
        _, out = jax.lax.associative_scan(_restart, (opened[-1], run[-1]))
        carry = jnp.concatenate([jnp.zeros((1,), out.dtype), out[:-1]])
        run = run + jnp.where(opened, 0, carry)
    with jax.named_scope("segsum.ends"):
        return jnp.where(has, jnp.take(run.reshape(-1), last), 0)


@dataclasses.dataclass
class EngineResult:
    authority: np.ndarray
    hub: np.ndarray
    iters: int
    residuals: np.ndarray
    converged: bool
    stale_events: int


class RankingEngine:
    _job_ids = itertools.count()  # the ``job`` id of each engine's spans

    def __init__(self, g: Graph, algorithm: str = "accel", n_shards: int = 8,
                 stale_limit: int = 0, straggler_prob: float = 0.0,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0, dtype=jnp.float64, seed: int = 0):
        self.g = g
        self.n = g.n_nodes
        self.n_shards = n_shards
        self.stale_limit = stale_limit
        self.straggler_prob = straggler_prob
        self.ckpt_dir = checkpoint_dir
        self.ckpt_every = checkpoint_every
        self.dtype = dtype
        self.rng = np.random.default_rng(seed)
        if algorithm not in ("accel", "hits"):
            raise ValueError(algorithm)
        self.job_id = next(RankingEngine._job_ids)
        with span("engine.build", job=self.job_id):
            with span("engine.partition"):
                parts = partition_edges(g, n_shards)
                host = [(parts["src"][s], parts["dst"][s],
                         parts["w"][s] * parts["mask"][s])
                        for s in range(n_shards)]
                weights = (accel_weights(g.indeg(), g.outdeg())
                           if algorithm == "accel" else None)
            # the host side of the transfers; their device side is not
            # waited for here
            with span("engine.upload"):
                dev = [(jnp.asarray(src), jnp.asarray(dst),
                        jnp.asarray(w, dtype)) for src, dst, w in host]
                self.ca, self.ch = ((None, None) if weights is None else
                                    (jnp.asarray(weights[0], dtype),
                                     jnp.asarray(weights[1], dtype)))
            # each shard's edges by destination (the authority pass) and by
            # source (the hub pass), sorted on the device
            with span("engine.order"):
                self.shards = [(_order(dst, src, w, self.n),
                                _order(src, dst, w, self.n))
                               for src, dst, w in dev]

    # ------------------------------------------------------------- internals
    def _sweep(self, h, cache_a, cache_h, staleness, force_fresh=False):
        """One sweep with per-shard straggler simulation."""
        stale_events = 0
        prob = 0.0 if force_fresh else self.straggler_prob
        hs = h if self.ch is None else h * self.ch
        partials_a = []
        for s, (by_dst, _) in enumerate(self.shards):
            straggles = (self.rng.random() < prob
                         and staleness[s] < self.stale_limit
                         and cache_a[s] is not None)
            if straggles:
                partials_a.append(cache_a[s])
                staleness[s] += 1
                stale_events += 1
            else:
                p = _segment_sum(hs, *by_dst)
                partials_a.append(p)
                cache_a[s] = p
                staleness[s] = 0
        a = sum(partials_a)
        as_ = a if self.ca is None else a * self.ca
        partials_h = []
        for s, (_, by_src) in enumerate(self.shards):
            straggles = (self.rng.random() < prob
                         and staleness[s] < self.stale_limit
                         and cache_h[s] is not None)
            if straggles:
                partials_h.append(cache_h[s])
                staleness[s] += 1
                stale_events += 1
            else:
                p = _segment_sum(as_, *by_src)
                partials_h.append(p)
                cache_h[s] = p
        h_new = sum(partials_h)
        h_new = h_new / (jnp.sum(jnp.abs(h_new)) + 1e-30)
        return h_new, a, stale_events

    # ------------------------------------------------------------------ API
    def run(self, tol: float = 1e-10, max_iter: int = 1000,
            resume: bool = False) -> EngineResult:
        h = jnp.full((self.n,), 1.0 / self.n, self.dtype)
        k0 = 0
        residuals = []
        if resume and self.ckpt_dir and ckpt_mod.latest_step(self.ckpt_dir) is not None:
            state, k0, extra = ckpt_mod.restore(self.ckpt_dir, {"h": np.asarray(h)})
            h = jnp.asarray(state["h"], self.dtype)
            residuals = list(extra.get("residuals", []))
        cache_a = [None] * self.n_shards
        cache_h = [None] * self.n_shards
        staleness = [0] * self.n_shards
        stale_total = 0
        converged = False
        a = jnp.zeros_like(h)
        k = k0
        confirming = False
        for k in range(k0 + 1, max_iter + 1):
            # once the residual dips below tol, confirm with fully-fresh
            # sweeps (no stale partials) — otherwise a shard stuck on its
            # cached product can fake convergence at the wrong point
            with span("engine.sweep", job=self.job_id, sweep=k):
                h_new, a, ev = self._sweep(h, cache_a, cache_h, staleness,
                                           force_fresh=confirming)
                with span("engine.sync"):  # the sweep's one host sync
                    delta = float(jnp.sum(jnp.abs(h_new - h)))
            stale_total += ev
            residuals.append(delta)
            h = h_new
            if self.ckpt_dir and self.ckpt_every and k % self.ckpt_every == 0:
                ckpt_mod.save(self.ckpt_dir, k, {"h": np.asarray(h)},
                              extra={"residuals": residuals[-20:]})
            if delta <= tol:
                if confirming or self.straggler_prob == 0.0:
                    converged = True
                    break
                confirming = True
            else:
                confirming = False
        a = a / (jnp.sum(jnp.abs(a)) + 1e-30)
        return EngineResult(np.asarray(a), np.asarray(h), k,
                            np.asarray(residuals), converged, stale_total)
