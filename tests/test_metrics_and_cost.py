"""Metrics vs scipy; HLO cost model vs XLA cost analysis."""
import jax
import jax.numpy as jnp
import numpy as np
import scipy.stats
from hypothesis import given, settings, strategies as st

from repro.core.metrics import cosine, spearman, topk_overlap
from repro.launch.hlo_cost import HloModule


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


def test_hlo_cost_matches_xla_loop_free():
    def f(a, b, c):
        return (a @ b) @ c + jnp.sum(a)
    A = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    B = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    C = jax.ShapeDtypeStruct((512, 64), jnp.float32)
    comp = jax.jit(f).lower(A, B, C).compile()
    mod = HloModule(comp.as_text())
    ca = comp.cost_analysis()
    assert abs(mod.flops() - ca["flops"]) / ca["flops"] < 0.05
    assert abs(mod.bytes_accessed() - ca["bytes accessed"]) / \
        ca["bytes accessed"] < 0.2


def test_hlo_cost_scales_with_scan_length():
    def g(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    flops = {}
    for L in (1, 4):
        W = jax.ShapeDtypeStruct((L, 128, 128), jnp.float32)
        X = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        comp = jax.jit(g).lower(W, X).compile()
        flops[L] = HloModule(comp.as_text()).flops()
    ratio = flops[4] / flops[1]
    assert 3.5 < ratio < 4.5, f"scan multiplier broken: {ratio}"
    # XLA's own analysis does NOT scale (the reason hlo_cost exists)
    # (documented behavior, not asserted — XLA may fix it someday)


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
from repro.launch.hlo_cost import HloModule
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("d",))
def f(x):
    return jax.shard_map(lambda xs: jax.lax.psum(xs, "d"), mesh=mesh,
                         in_specs=P("d", None), out_specs=P())(x)
X = jax.ShapeDtypeStruct((8, 128), jnp.float32)
comp = jax.jit(f).lower(X).compile()
cb = HloModule(comp.as_text()).collective_bytes()
assert cb["n_collective_ops"] >= 1, cb
assert cb["total_bytes"] > 0, cb
print("OK", cb["total_bytes"])
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from the table for the named chip; a chip that is not in
    the table is an error, never a silent v5e default."""
    import pytest

    from repro.launch.hlo_analysis import Roofline, peaks
    v5e = peaks("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    rl = Roofline(197e12, 0.0, 0.0, 1, "TPU v5 lite", model_flops=197e12)
    assert rl.compute_s == 1.0 and rl.bottleneck == "compute"
    with pytest.raises(ValueError, match="cpu"):
        peaks("cpu")
    with pytest.raises(ValueError):
        Roofline(1.0, 1.0, 0.0, 1, "TPU v9 imaginary")
