"""Checkpoint/restore, straggler tolerance, elasticity."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ck
from repro.core import accel_hits
from repro.core.engine import RankingEngine
from repro.graph import WebGraphSpec, generate_webgraph


def test_checkpoint_roundtrip(tmp_path):
    ks = jax.random.split(jax.random.key(0), 3)
    params = {"h": jax.random.uniform(ks[0], (40,), jnp.float64),
              "blocks": [jax.random.normal(ks[1], (4, 8), jnp.float32),
                         jax.random.normal(ks[2], (8,), jnp.float32)]}
    opt = {"step": jnp.asarray(3, jnp.int32),
           "staleness": np.arange(8, dtype=np.int64)}
    ck.save(str(tmp_path), 7, {"params": params, "opt": opt},
            extra={"note": "x"})
    tree, step, extra = ck.restore(str(tmp_path),
                                   {"params": params, "opt": opt})
    assert step == 7 and extra["note"] == "x"
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(
            {"params": params, "opt": opt})):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_prune_and_latest(tmp_path):
    params = {"w": jnp.ones((3,))}
    for s in (1, 2, 3, 4):
        ck.save(str(tmp_path), s, params)
    assert ck.latest_step(str(tmp_path)) == 4
    ck.prune(str(tmp_path), keep=2)
    assert ck.latest_step(str(tmp_path)) == 4
    assert len([d for d in os.listdir(tmp_path) if d.startswith("step_")]) == 2


def test_junk_step_dirs_read_as_absent(tmp_path):
    """Regression: a stray non-numeric ``step_*`` dir (backup copy, editor
    dropping) used to ValueError out of ``int(name[5:])`` in latest_step
    and prune — bricking every reader that scans the directory, including
    restart-restore. Junk must be skipped, not fatal, and never deleted."""
    params = {"w": jnp.ones((3,))}
    for s in (1, 2):
        ck.save(str(tmp_path), s, params)
    os.makedirs(tmp_path / "step_backup")
    (tmp_path / "step_backup" / "manifest.json").write_text("{}")
    os.makedirs(tmp_path / "step_12.orig")
    assert ck.latest_step(str(tmp_path)) == 2
    ck.prune(str(tmp_path), keep=1)
    assert ck.latest_step(str(tmp_path)) == 2
    assert (tmp_path / "step_backup").is_dir()  # junk untouched by prune
    assert (tmp_path / "step_12.orig").is_dir()
    tree, step, _ = ck.restore(str(tmp_path), params)
    assert step == 2


def test_engine_straggler_tolerance():
    g = generate_webgraph(WebGraphSpec(300, 2200, 0.6, seed=13))
    ref = accel_hits(g, tol=1e-11)
    eng = RankingEngine(g, "accel", n_shards=4, stale_limit=2,
                        straggler_prob=0.25, seed=17)
    r = eng.run(tol=1e-11, max_iter=3000)
    assert r.converged and r.stale_events > 0
    assert np.abs(r.hub - ref.v).max() < 1e-9


def test_engine_elastic_reshard(tmp_path):
    g = generate_webgraph(WebGraphSpec(250, 1800, 0.5, seed=19))
    ref = accel_hits(g, tol=1e-11)
    eng = RankingEngine(g, "accel", n_shards=4, checkpoint_dir=str(tmp_path),
                        checkpoint_every=2)
    eng.run(tol=1e-11, max_iter=4)  # preempted early
    eng2 = RankingEngine(g, "accel", n_shards=16,
                         checkpoint_dir=str(tmp_path))  # new world size
    r = eng2.run(tol=1e-11, resume=True)
    assert r.converged
    assert np.abs(r.hub - ref.v).max() < 1e-9
