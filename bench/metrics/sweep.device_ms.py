"""Device busy time in the traced window per batch swept."""
from bench.readers import busy_ms_per


def read(run):
    return busy_ms_per(run, run.get("svc_delta", {}).get(
        ("pipeline.swept", None), 0))
