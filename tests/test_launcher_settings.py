"""What the launchers run with when given no flags, and that every flag the
served configuration reads reaches its field.

The query cells serve ``service_config(build_parser().parse_args([]))``
and the rank cell runs ``make_engine`` over its own flags, so these tables
are the settings the benchmark measures: a change to any of them is a
change to what is served, not a refactor.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import Graph
from repro.launch import rank, serve_rank
from repro.serve import RankServiceConfig

# service_config(build_parser().parse_args([])), field by field
SERVED = {
    "v_max": 8,
    "out_cap": 32,
    "in_cap": 32,
    "tol": 1e-10,
    "max_iter": 1000,
    "rank_k": 0,
    "stable_sweeps": 2,
    "cache_size": 512,
    "warm_min_overlap": 0.5,
    "dtype": jnp.float64,
    "sweep_dtype": "",
    "polish_tol": None,
    "backend": "auto",
    "shard_mode": "dual_blocked",
    "shard_devices": None,
    "bsr_block": 128,
    "interpret": None,
    "bsr_fused": True,
    "plan_cache_size": 64,
    "lumping": "off",
    "pipeline_depth": 2,
    "deadline_ms": 5.0,
    "queue_depth": None,
    "shed_priority": 1,
    "spill_dir": None,
    "spill_policy": "all",
    "spill_keep_generations": 1,
}

# flag and a value other than its default -> (field, the value served)
FLAGS = {
    "--v": (["--v", "4"], "v_max", 4),
    "--tol": (["--tol", "1e-08"], "tol", 1e-8),
    "--backend": (["--backend", "dense"], "backend", "dense"),
    "--shard-mode": (["--shard-mode", "replicated"], "shard_mode",
                     "replicated"),
    "--shard-devices": (["--shard-devices", "2"], "shard_devices", 2),
    "--plan-cache": (["--plan-cache", "0"], "plan_cache_size", 0),
    "--bsr-host-loop": (["--bsr-host-loop"], "bsr_fused", False),
    "--pipeline-depth": (["--pipeline-depth", "1"], "pipeline_depth", 1),
    "--sweep-dtype": (["--sweep-dtype", "fp32"], "sweep_dtype", "fp32"),
    "--polish-tol": (["--polish-tol", "1e-09"], "polish_tol", 1e-9),
    "--lumping": (["--lumping", "auto"], "lumping", "auto"),
    "--rank-k": (["--rank-k", "4"], "rank_k", 4),
    "--stable-sweeps": (["--stable-sweeps", "3"], "stable_sweeps", 3),
    "--deadline-ms": (["--deadline-ms", "2.5"], "deadline_ms", 2.5),
    "--queue-depth": (["--queue-depth", "12"], "queue_depth", 12),
    "--shed-priority": (["--shed-priority", "2"], "shed_priority", 2),
    "--spill-policy": (["--spill-policy", "evict"], "spill_policy",
                       "evict"),
    "--spill-keep-generations": (["--spill-keep-generations", "3"],
                                 "spill_keep_generations", 3),
}


def _served(argv):
    return serve_rank.service_config(
        serve_rank.build_parser().parse_args(argv))


def test_the_table_names_every_field():
    assert set(SERVED) == {f.name for f in
                           dataclasses.fields(RankServiceConfig)}


@pytest.mark.parametrize("field", sorted(SERVED))
def test_served_setting(field):
    got = getattr(_served([]), field)
    want = SERVED[field]
    assert got == want and type(got) is type(want), (field, got, want)


@pytest.mark.parametrize("flag", sorted(FLAGS))
def test_flag_reaches_setting(flag):
    argv, field, want = FLAGS[flag]
    assert SERVED[field] != want  # the case moves the setting
    assert getattr(_served(argv), field) == want


@pytest.fixture(scope="module")
def rank_cell_engine():
    """The engine the rank cell's flags build, over a small graph."""
    rng = np.random.default_rng(0)
    src = rng.integers(0, 64, 400)
    dst = rng.integers(0, 64, 400)
    args = rank.build_parser().parse_args(
        ["--dataset", "stanford", "--scale", "1.0", "--backbutton"])
    return rank.make_engine(Graph(64, src, dst), args)


ENGINE = {
    "n_shards": lambda e: e.n_shards == 8,
    "stale_limit": lambda e: e.stale_limit == 0,
    "straggler_prob": lambda e: e.straggler_prob == 0.0,
    "ckpt_dir": lambda e: e.ckpt_dir is None,
    "dtype": lambda e: e.dtype == jnp.float64,
    "accel_weights": lambda e: e.ca is not None and e.ch is not None,
}


@pytest.mark.parametrize("attr", sorted(ENGINE))
def test_engine_setting(rank_cell_engine, attr):
    assert ENGINE[attr](rank_cell_engine)
