"""repro: accelerated HITS as a ranking system in JAX.

Reproduces and extends Mirzal & Furukawa (2009), "A Method for Accelerating
the HITS Algorithm": whole-graph ranking (``core.engine``, run by
``launch.rank``) and query-time HITS served in batches over a live index
(``serve.RankService``, run by ``launch.serve_rank``). ``docs/ARCHITECTURE.md``
is the system map.
"""

__version__ = "1.0.0"
