"""Useful bytes of the whole-graph sweeps in the traced window
(``work.py``, at the reference's sweep count) over what HBM could move in
the device's busy time."""
from bench.readers import roofline_pct


def read(run):
    return roofline_pct(run)
