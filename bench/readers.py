"""Arithmetic shared by the metric readers under ``bench/metrics/``.

Each reader is ``read(run) -> number | None`` over the run's record (see
``harness.run_cell``); these helpers return None where the run has
nothing to read, so the harness leaves that metric out of the line.
"""
from __future__ import annotations

from bench import loadgen, trace_reduce


def hist_mean(delta: dict, name: str, label=None):
    """Mean of a telemetry histogram's observations inside the window
    (its count and sum, differenced across the window)."""
    count, total = delta.get((name, label), (0, 0.0))
    return total / count if count else None


def latency_pct(run: dict, q: float):
    lat = run.get("latencies_ms")
    if lat is None or len(lat) == 0:
        return None
    v = loadgen.percentile(lat, q)
    return v if v < float("inf") else None


def device_idle_pct(run: dict):
    rec = run.get("trace")
    if rec is None or not rec["device"]:
        return None
    return 100.0 * trace_reduce.idle_share(rec)


def busy_ms_per(run: dict, count):
    rec = run.get("trace")
    if rec is None or not rec["device"] or not count:
        return None
    return 1e3 * trace_reduce.busy_s(rec) / count


def roofline_pct(run: dict):
    """Useful bytes over what the chip's HBM could move in the device's
    busy time."""
    rec, peaks = run.get("trace"), run.get("peaks")
    moved = run.get("useful_bytes")
    if rec is None or not rec["device"] or peaks is None or not moved:
        return None
    busy = trace_reduce.busy_s(rec)
    return 100.0 * moved / (busy * peaks["hbm_bytes_per_s"]) if busy else None
