"""Mean wall time of building a roll's next edge list and weight table
in the window: the program's ``delta.apply`` span
(``service.delta.apply_ms``)."""
from bench.readers import hist_mean


def read(run):
    return hist_mean(run.get("svc_delta", {}), "service.delta.apply_ms")
