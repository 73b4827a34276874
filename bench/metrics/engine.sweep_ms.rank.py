"""Device busy time in the traced window per engine sweep."""
from bench.readers import busy_ms_per


def read(run):
    return busy_ms_per(run, sum(j["sweeps"] for j in run.get("jobs", [])))
