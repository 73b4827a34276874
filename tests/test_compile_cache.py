"""The entry points' persistent compile cache: placed by
``JAX_COMPILATION_CACHE_DIR`` when it is set, else at the checkout's own
``.jax_cache``. Each check runs in a child process, so the test process
itself never turns the cache on."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = r"""
import jax, jax.numpy as jnp
from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
path = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == path, path
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print("CACHE", path, DEFAULT_DIR)
"""


def _probe(**env_extra):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    r = subprocess.run([sys.executable, "-c", PROBE], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split("CACHE")[1].split()


def test_env_dir_wins_and_receives_entries(tmp_path):
    where = tmp_path / "xla_cache"
    path, _default = _probe(JAX_COMPILATION_CACHE_DIR=str(where))
    assert path == str(where)
    assert any(where.iterdir()), "no cache entry written"


def test_unset_env_uses_fixed_checkout_dir():
    # the cache stays off here so nothing is written into the checkout
    path, default = _probe(JAX_ENABLE_COMPILATION_CACHE="false")
    assert path == default == os.path.join(ROOT, ".jax_cache")
