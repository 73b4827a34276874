"""From a profiler trace to device busy time, idle share and breakdowns.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
a small JSON-able record; everything after that is plain arithmetic on
intervals, kept here so that every run computes the same numbers the same
way. Times are nanoseconds on the trace's own clock.

The record:

- ``device``: ``[chip, op name, start, duration]`` for every operation on
  a TPU core's op line (``XLA Ops``; a plane without one gives its
  ``XLA Modules``). A loop's op spans the ops of its body, so ops nest;
- ``programs``: ``[chip, program name, start, duration]`` from the
  ``XLA Modules`` line, the compiled programs the ops ran in;
- ``window``: ``[start, end]`` of the benchmark's ``bench.window`` host
  annotation, the traced window;
- ``host``: ``[label, start, end]`` spans the benchmark adds afterwards,
  shifted onto the trace's clock.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

WINDOW_MARK = "bench.window"
OP_LINES = ("XLA Ops", "XLA Modules")


def read_xplane(trace_dir: str) -> dict:
    """The record of the newest trace under ``trace_dir``."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    device, programs, window = [], [], None
    chip = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            for name in OP_LINES:
                if name in lines:
                    device += [[chip, ev.name, float(ev.start_ns),
                                float(ev.duration_ns)]
                               for ev in lines[name].events]
                    break
            if OP_LINES[1] in lines:
                programs += [[chip, ev.name, float(ev.start_ns),
                              float(ev.duration_ns)]
                             for ev in lines[OP_LINES[1]].events]
            chip += 1
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == WINDOW_MARK:
                        window = [float(ev.start_ns), float(ev.end_ns)]
    return {"device": device, "programs": programs, "window": window,
            "host": []}


def union_ns(intervals) -> float:
    """Length covered by the union of ``[start, end]`` intervals."""
    iv = sorted((float(s), float(e)) for s, e in intervals if e > s)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in iv:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _clip(rec: dict, chip=None):
    lo, hi = rec["window"]
    out = []
    for c, _name, s, d in rec["device"]:
        if chip is not None and c != chip:
            continue
        s2, e2 = max(s, lo), min(s + d, hi)
        if e2 > s2:
            out.append((s2, e2))
    return out


def chips(rec: dict) -> list:
    return sorted({int(ev[0]) for ev in rec["device"]})


def window_s(rec: dict) -> float:
    lo, hi = rec["window"]
    return (hi - lo) * 1e-9


def busy_s(rec: dict) -> float:
    """Seconds in which some operation ran on a chip, inside the window,
    averaged over the chips that ran anything."""
    cs = chips(rec)
    if not cs:
        return 0.0
    return float(np.mean([union_ns(_clip(rec, c)) for c in cs])) * 1e-9


def idle_share(rec: dict) -> float:
    """1 - busy / window."""
    return 1.0 - busy_s(rec) / window_s(rec)


def _short(op: str) -> str:
    """``%fusion.2`` of ``%fusion.2 = f32[8] fusion(...)``."""
    return op.split(" = ", 1)[0].strip()


def _program(name: str) -> str:
    """``jit_f`` of ``jit_f(1234)``: the fingerprint differs per shape."""
    return re.sub(r"\(\d+\)$", "", name)


def leaf_ops(rec: dict) -> list:
    """The device events that hold no other event of their chip: the ops
    that did the work, without the loops around them."""
    out = []
    by_chip: dict = {}
    for ev in rec["device"]:
        by_chip.setdefault(ev[0], []).append(ev)
    for evs in by_chip.values():
        evs = sorted(evs, key=lambda e: (e[2], -e[3]))
        for i, (c, name, s, d) in enumerate(evs):
            nxt = evs[i + 1][2] if i + 1 < len(evs) else float("inf")
            if nxt >= s + d:
                out.append((c, name, s, d))
    return out


def top_ops(rec: dict, k: int = 10) -> list:
    """``[[program/op, seconds]]`` of the ``k`` leaf operations that took
    most device time in the window, summed over their calls, shapes and
    chips. An op is named by the program it ran in and its HLO name."""
    lo, hi = rec["window"]
    progs = {}
    for c, name, s, d in rec.get("programs", []):
        progs.setdefault(c, []).append((s, s + d, _program(name)))
    for c in progs:
        progs[c].sort()
    tot: dict = {}
    for c, name, s, d in leaf_ops(rec):
        t = min(s + d, hi) - max(s, lo)
        if t <= 0:
            continue
        where = "?"
        for p0, p1, pname in progs.get(c, ()):
            if p0 <= s < p1:
                where = pname
                break
            if p0 > s:
                break
        key = f"{where}/{_short(name)}"
        tot[key] = tot.get(key, 0.0) + t
    return [[n, t * 1e-9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(rec: dict, chip: int = 0) -> list:
    """``[start, end]`` of every stretch of the window in which ``chip``
    ran nothing."""
    lo, hi = rec["window"]
    gaps, cur = [], lo
    for s, e in sorted(_clip(rec, chip)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def idle_by_host(rec: dict, k: int = 10, chip: int = 0) -> list:
    """``[[host activity, seconds]]``: the chip's idle time split by the
    host span that covered it (``rec["host"]``); idle time that no span
    covers is ``"no host span"``. Where spans overlap, each takes the part
    no earlier-listed span took. Largest first, at most ``k``."""
    out: dict = {}
    spans = [(lbl, float(s), float(e)) for lbl, s, e in rec["host"]]
    for g0, g1 in idle_gaps(rec, chip):
        left = [(g0, g1)]
        for lbl, s, e in spans:
            rest = []
            for a, b in left:
                c0, c1 = max(a, s), min(b, e)
                if c1 > c0:
                    out[lbl] = out.get(lbl, 0.0) + (c1 - c0)
                    if c0 > a:
                        rest.append((a, c0))
                    if b > c1:
                        rest.append((c1, b))
                else:
                    rest.append((a, b))
            left = rest
        for a, b in left:
            out["no host span"] = out.get("no host span", 0.0) + (b - a)
    return [[n, t * 1e-9] for n, t in
            sorted(out.items(), key=lambda kv: -kv[1])[:k]]
