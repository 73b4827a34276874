"""Production ranking engine: sharded power iteration with checkpointing,
bounded-staleness straggler tolerance, and elastic re-sharding.

The engine partitions edges into ``n_shards`` virtual shards (on hardware,
one per host/slice; here executed sequentially — the combine semantics are
identical). Per sweep each shard contributes a partial authority/hub
product; the combine is a sum, so the engine tolerates:

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


@partial(jax.jit, static_argnames=("n",))
def _partial_a(h_scaled, src, dst, w, n):
    with jax.named_scope("segsum.gather"):
        x = jnp.take(h_scaled, src) * w
    with jax.named_scope("segsum.scatter"):
        return jax.ops.segment_sum(x, dst, num_segments=n)


@partial(jax.jit, static_argnames=("n",))
def _partial_h(a_scaled, src, dst, w, n):
    with jax.named_scope("segsum.gather"):
        x = jnp.take(a_scaled, dst) * w
    with jax.named_scope("segsum.scatter"):
        return jax.ops.segment_sum(x, src, num_segments=n)


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
                self.shards = [(jnp.asarray(src), jnp.asarray(dst),
                                jnp.asarray(w, dtype))
                               for src, dst, w in host]
                self.ca, self.ch = ((None, None) if weights is None else
                                    (jnp.asarray(weights[0], dtype),
                                     jnp.asarray(weights[1], dtype)))

    # ------------------------------------------------------------- internals
    def _sweep(self, h, cache_a, cache_h, staleness, force_fresh=False):
        """One sweep with per-shard straggler simulation."""
        stale_events = 0
        prob = 0.0 if force_fresh else self.straggler_prob
        hs = h if self.ch is None else h * self.ch
        partials_a = []
        for s, (src, dst, w) in enumerate(self.shards):
            straggles = (self.rng.random() < prob
                         and staleness[s] < self.stale_limit
                         and cache_a[s] is not None)
            if straggles:
                partials_a.append(cache_a[s])
                staleness[s] += 1
                stale_events += 1
            else:
                p = _partial_a(hs, src, dst, w, self.n)
                partials_a.append(p)
                cache_a[s] = p
                staleness[s] = 0
        a = sum(partials_a)
        as_ = a if self.ca is None else a * self.ca
        partials_h = []
        for s, (src, dst, w) in enumerate(self.shards):
            straggles = (self.rng.random() < prob
                         and staleness[s] < self.stale_limit
                         and cache_h[s] is not None)
            if straggles:
                partials_h.append(cache_h[s])
                staleness[s] += 1
                stale_events += 1
            else:
                p = _partial_h(as_, src, dst, w, self.n)
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
