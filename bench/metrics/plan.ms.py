"""Mean wall time of the pipeline's plan stage inside the window."""
from bench.readers import hist_mean


def read(run):
    return hist_mean(run.get("svc_delta", {}), "pipeline.stage_ms", "plan")
