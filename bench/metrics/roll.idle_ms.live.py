"""Device idle time inside the graph rolls per roll, in the traced
window: the rolls are the program's ``delta.roll`` spans (``rec["host"]``
entries ``bench/drivers/serve_live.py`` copies from
``RankService.delta_trace``), read as ``sweep.idle_ms`` reads the
pipeline's sweeps."""
from bench import spans


def read(run):
    rec = run.get("trace")
    if rec is None or not rec["device"]:
        return None
    rolls = [(s, e) for label, s, e in rec["host"] if label == "delta.roll"]
    return 1e-6 * spans.idle_in(rec, rolls) / len(rolls) if rolls else None
