"""Metrics vs scipy; collective bytes parsed from compiled HLO."""
import numpy as np
import scipy.stats
from hypothesis import given, settings, strategies as st

from repro.core.metrics import cosine, spearman, topk_overlap


@given(st.lists(st.floats(-100, 100), min_size=3, max_size=60),
       st.integers(0, 5))
@settings(max_examples=50, deadline=None)
def test_spearman_matches_scipy(xs, seed):
    rng = np.random.default_rng(seed)
    x = np.array(xs)
    y = rng.permutation(x) + rng.normal(0, 1e-3, len(x))
    ours = spearman(x, y)
    ref = scipy.stats.spearmanr(x, y).statistic
    if np.isnan(ref):
        return
    assert abs(ours - ref) < 1e-6


def test_cosine_basic():
    assert np.isclose(cosine(np.array([1, 0]), np.array([1, 0])), 1.0)
    assert np.isclose(cosine(np.array([1, 0]), np.array([0, 1])), 0.0)


def test_topk_overlap():
    x = np.arange(100.0)
    assert topk_overlap(x, x, 10) == 1.0
    assert topk_overlap(x, -x, 10) == 0.0


def test_collective_bytes_parse():
    import os
    import subprocess
    import sys
    # collectives need >1 device: run in a subprocess with 4 host devices
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.sparse.dist import collective_bytes
mesh = make_mesh((4,), ("d",))
def f(x):
    return jax.shard_map(lambda xs: jax.lax.psum(xs, "d"), mesh=mesh,
                         in_specs=P("d", None), out_specs=P())(x)
X = jax.ShapeDtypeStruct((8, 128), jnp.float32)
comp = jax.jit(f).lower(X).compile()
cb = collective_bytes(comp.as_text())
# one all-reduce of each device's f32[2,128], counted once
assert cb == {"total_bytes": 1024.0, "by_kind": {"all-reduce": 1024.0},
              "n_collective_ops": 1}, cb
print("OK", cb["total_bytes"])
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout

