"""99th percentile of how late the load generator sent a request."""
from bench import loadgen


def read(run):
    late = run.get("lateness_ms")
    if late is None or len(late) == 0:
        return None
    return loadgen.percentile(late, 99)
