"""Imports point down the layers: graph < sparse, kernels < core (with
checkpoint) < serve < launch.

Each case imports one package with all of its modules (or one launcher,
driven as far as its flags) in a fresh CPU interpreter and checks which
``repro`` layers that pulled in. It also reads every import statement in
the package's source, those inside functions too, which run only when
called.
"""
import ast
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BELOW_CORE = {"graph", "sparse", "kernels", "checkpoint"}
_BELOW_SERVE = _BELOW_CORE | {"core"}
_BELOW_LAUNCH = _BELOW_SERVE | {"serve"}

# the layers each package's modules may import, its own included
ALLOWED = {
    "repro.graph": {"graph"},
    "repro.sparse": {"graph", "sparse"},
    "repro.kernels": {"graph", "kernels"},
    "repro.checkpoint": {"checkpoint"},
    "repro.core": _BELOW_SERVE,
    "repro.serve": _BELOW_LAUNCH,
    "repro.launch.rank": _BELOW_LAUNCH | {"launch"},
    "repro.launch.serve_rank": _BELOW_LAUNCH | {"launch"},
}

# upward imports still in the tree, each a named debt (ROADMAP, queue 3):
# the whole-graph engine times its spans with the service's span
EXCEPTIONS = {
    "repro.core": {"repro.serve", "repro.serve.telemetry"},
}

_PACKAGE = """
import importlib, pkgutil
pkg = importlib.import_module({name!r})
for info in pkgutil.iter_modules(pkg.__path__):
    importlib.import_module(pkg.__name__ + "." + info.name)
"""

_LAUNCHER = {
    "repro.launch.rank": """
import numpy as np
from repro.graph import Graph
from repro.launch import rank
args = rank.build_parser().parse_args([])
rank.make_engine(Graph(4, np.array([0, 1, 2]), np.array([1, 2, 3])), args)
""",
    "repro.launch.serve_rank": """
from repro.launch import serve_rank
serve_rank.service_config(serve_rank.build_parser().parse_args([]))
""",
}

_REPORT = """
import json, sys
print(json.dumps(sorted(m for m in sys.modules if m.startswith("repro."))))
"""


def _loaded(name):
    code = _LAUNCHER.get(name) or _PACKAGE.format(name=name)
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    r = subprocess.run([sys.executable, "-c", code + _REPORT],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.splitlines()[-1])


def _written(name):
    """``repro`` modules named by the import statements in ``name``'s
    source files."""
    path = os.path.join(ROOT, "src", *name.split("."))
    files = ([path + ".py"] if os.path.isfile(path + ".py") else
             [os.path.join(path, f) for f in sorted(os.listdir(path))
              if f.endswith(".py")])
    out = set()
    for f in files:
        here = (name if f.endswith("__init__.py") or os.path.isdir(path)
                else name.rsplit(".", 1)[0]).split(".")
        for node in ast.walk(ast.parse(open(f).read())):
            if isinstance(node, ast.Import):
                out |= {a.name for a in node.names}
            elif isinstance(node, ast.ImportFrom):
                base = (here[:len(here) - node.level + 1] if node.level
                        else [])
                mod = ".".join(base + ([node.module] if node.module else []))
                out |= ({mod} if node.module else
                        {f"{mod}.{a.name}" for a in node.names})
    return {m for m in out if m.startswith("repro.")}


@pytest.mark.parametrize("name", sorted(ALLOWED))
def test_imports_point_down(name):
    loaded = _loaded(name)
    assert name in loaded
    extra = EXCEPTIONS.get(name, set())
    upward = sorted(m for m in set(loaded) | _written(name)
                    if m not in extra
                    and m.split(".")[1] not in ALLOWED[name])
    assert not upward, f"{name} imports above its layer: {upward}"
