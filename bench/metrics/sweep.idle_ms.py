"""Device idle time inside the pipeline's sweep stages per batch swept,
in the traced window: the sweep stages are the pipeline's own ``sweep``
spans (``rec["host"]``), the same intervals as its ``pipeline.sweep``
annotations."""
from bench import spans


def read(run):
    rec = run.get("trace")
    swept = run.get("svc_delta", {}).get(("pipeline.swept", None), 0)
    if rec is None or not rec["device"] or not swept:
        return None
    sweeps = [(s, e) for label, s, e in rec["host"] if label == "sweep"]
    return 1e-6 * spans.idle_in(rec, sweeps) / swept if sweeps else None
