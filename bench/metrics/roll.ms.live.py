"""Mean wall time of a graph roll in the window: the program's
``delta.roll`` span, from the call to ``apply_edge_delta`` to its
acknowledgement (``service.delta.roll_ms``)."""
from bench.readers import hist_mean


def read(run):
    return hist_mean(run.get("svc_delta", {}), "service.delta.roll_ms")
