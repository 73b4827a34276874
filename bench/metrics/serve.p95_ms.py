"""95th percentile latency of every request sent in the window, from when
it was due to when its ticket resolved; a failed request counts as
infinitely late. Above capacity the queue grows all through the run, so
this swings with the smallest change: a layer's reading, not a bound."""
from bench.readers import latency_pct


def read(run):
    return latency_pct(run, 95)
