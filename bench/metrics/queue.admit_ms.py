"""Mean time a ``submit`` call took from entry to admission inside the
window, the queue's backpressure wait included, from the queue's
``queue.admit_ms`` histogram (None where the program has none)."""
from bench.readers import hist_mean


def read(run):
    return hist_mean(run.get("queue_delta", {}), "queue.admit_ms")
