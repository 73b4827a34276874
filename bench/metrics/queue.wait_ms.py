"""Mean wait in the queue (admission to dispatch) inside the window, from
the queue's ``queue.wait_ms`` histogram."""
from bench.readers import hist_mean


def read(run):
    return hist_mean(run.get("queue_delta", {}), "queue.wait_ms")
