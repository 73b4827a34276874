"""The query-ranking service. Names load from their modules on first use,
so that importing one light module (``serve.telemetry``, which the
whole-graph engine uses for its spans) does not import the sweep
backends and, through them, Pallas."""
import importlib

_EXPORTS = {
    "backends": ("BACKENDS", "BsrSweepBackend", "DenseSweepBackend",
                 "ShardedSweepBackend", "SweepBackend", "SweepBatch",
                 "make_backend", "select_backend", "shared_mesh"),
    "pipeline": ("PipelineJob", "ServePipeline"),
    "plans": ("BsrPlan", "DensePlan", "PlanCache", "ShardedPlan", "SweepPlan",
              "structure_key"),
    "queue": ("QueueTicket", "RankQueue"),
    "rank_service": ("QueryResult", "RankService", "RankServiceConfig"),
    "spill": ("CacheSpill", "PlanSpill"),
    "telemetry": ("Counter", "Gauge", "Histogram", "MetricsRegistry",
                  "StatsServer"),
}
_MODULE_OF = {name: mod for mod, names in _EXPORTS.items() for name in names}

__all__ = sorted(_MODULE_OF)


def __getattr__(name):
    mod = _MODULE_OF.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(f".{mod}", __name__), name)
    globals()[name] = value
    return value
