"""Distributed sweeps under shard_map (subprocess: needs >1 host device)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=600)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    return r.stdout


DIST_EQUIV = r"""
import numpy as np, jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from repro.graph import generate_webgraph, WebGraphSpec
from repro.sparse.dist import build_edge_shards, make_dist_hits_sweep, blocked_to_full
from repro.core import accel_hits, accel_weights

g = generate_webgraph(WebGraphSpec(200, 1500, 0.6, seed=1))
ref = accel_hits(g, tol=1e-12, dtype=jnp.float64)
ca, ch = accel_weights(g.indeg(), g.outdeg())
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
for mode in ["replicated", "dual_blocked", "dual_blocked_compact"]:
    shards = build_edge_shards(g, 8, mode)
    sweep, h0, args = make_dist_hits_sweep(mesh, shards, g.n_nodes,
        axes=("data", "model"), ca=ca, ch=ch, dtype=jnp.float64)
    with jax.set_mesh(mesh):
        sweep_j = jax.jit(sweep)
        h = h0
        for _ in range(60):
            h, a = sweep_j(h, *args)
    if mode == "dual_blocked_compact":
        h_c = np.asarray(h).reshape(-1)[:shards["n_hub"]].copy()
        hf = np.zeros(g.n_nodes)
        hf[shards["nd_ids"]] = h_c
    elif mode == "dual_blocked":
        hf = blocked_to_full(h, g.n_nodes)
    else:
        hf = np.asarray(h)
    err = np.abs(hf - ref.v).max()
    assert err < 1e-12, (mode, err)
print("DIST OK")
"""

RING = r"""
import numpy as np, jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from jax.sharding import PartitionSpec as P
from repro.sparse.dist import ring_allreduce_chunked
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
spec = P("data", None)
f1 = jax.shard_map(lambda xs: ring_allreduce_chunked(xs[0], "data", 3)[None],
                   mesh=mesh, in_specs=spec, out_specs=spec)
f2 = jax.shard_map(lambda xs: jax.lax.psum(xs[0], "data")[None],
                   mesh=mesh, in_specs=spec, out_specs=spec)
x = jax.random.normal(jax.random.key(0), (8, 53), jnp.float64)
with jax.set_mesh(mesh):
    assert np.allclose(jax.jit(f1)(x), jax.jit(f2)(x))
print("RING OK")
"""

@pytest.mark.parametrize("name,code", [
    ("dist_equivalence", DIST_EQUIV),
    ("ring_allreduce", RING),
])
def test_distributed(name, code):
    out = _run(code)
    assert "OK" in out
