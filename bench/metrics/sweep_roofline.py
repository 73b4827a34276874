"""Useful bytes of the queries swept in the traced window (``work.py``)
over what HBM could move in the device's busy time."""
from bench.readers import roofline_pct


def read(run):
    return roofline_pct(run)
