"""Share of the traced window in which the chip ran no operation."""
from bench.readers import device_idle_pct


def read(run):
    return device_idle_pct(run)
