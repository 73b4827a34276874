"""The program's own spans on the device trace's clock.

The program opens a ``jax.profiler.TraceAnnotation`` at each layer
boundary (``serve.telemetry.span``): ``queue.*``, ``pipeline.*``,
``backend.*`` and ``engine.*`` events on the profiler's host plane, with
their ids (run, batch, job, sweep) as event arguments. Its device ops
carry named scopes (``hits.*``, ``segsum.*``) in their event stats. This
module reads both from an ``.xplane.pb`` and splits device idle time by
span, in nanoseconds on the trace's own clock, beside
``trace_reduce``'s record:

- ``spans``: ``[name, start, end, thread, ids]``, ``thread`` the index of
  the host line (one per thread) the span was on;
- ``op_scopes``: ``{device op name: [scope, stat]}``, the named scopes
  in the op's stats joined by ``/`` (``""`` for none) and the stat that
  carried them;
- ``op_stats``: ``[[stat, value]]`` of one device op's metadata (one
  with a scope where any has one), to show where its names are kept.

A program without spans reads as no spans, and its idle time all as
``no program span``.
"""
from __future__ import annotations

import glob
import heapq
import os
import re

from bench import trace_reduce

SPAN_NAME = re.compile(r"^(queue|pipeline|backend|engine)\.[a-z_]+$")
SCOPE = re.compile(r"^(hits|segsum)\.[a-z_]+$")
NO_SPAN = "no program span"


def read(trace_dir: str) -> dict:
    """``{"spans": ..., "op_scopes": ..., "op_stats": ...}`` of the newest
    trace under ``trace_dir``."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    path = max(paths, key=os.path.getmtime)
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, ln in enumerate(plane.lines):
                spans += [[ev.name, float(ev.start_ns), float(ev.end_ns), i,
                           dict(ev.stats)]
                          for ev in ln.events if SPAN_NAME.match(ev.name)]
    spans.sort(key=lambda s: s[1])
    with open(path, "rb") as f:
        op_scopes, op_stats = _device_op_scopes(f.read())
    return {"spans": spans, "op_scopes": op_scopes, "op_stats": op_stats}


# The xplane is an ``XSpace`` protobuf. An op's HLO metadata (its op name,
# which holds the named scopes) is kept in the stats of the op's event
# metadata, which ``ProfileData`` does not expose; these few fields of
# tsl/profiler/protobuf/xplane.proto are read from the wire format.
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 4, 5
_META_NAME, _META_DISPLAY, _META_STATS = 2, 4, 5
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """``(field, value)`` of the message in ``buf[lo:hi]``: an int for a
    varint, ``(start, end)`` for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _device_op_scopes(buf: bytes):
    """``({op name: [scope, stat]}, [[stat, value]] of one op)`` over the
    TPU planes' event metadata; an op is found under its name and its
    display name."""
    scopes, sample = {}, []
    for field, plane in _fields(buf, 0, len(buf)):
        if field != _SPACE_PLANES:
            continue
        fields = list(_fields(buf, *plane))
        name = next((_text(buf, v) for f, v in fields if f == _PLANE_NAME), "")
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for f, entry in fields:
            if f == _PLANE_STAT_META:
                for k, v in _fields(buf, *entry):
                    if k == 2:  # the map entry's value: an XStatMetadata
                        meta = dict(_fields(buf, *v))
                        stat_names[meta.get(1, 0)] = _text(buf, meta[2]) \
                            if 2 in meta else ""
        for f, entry in fields:
            if f != _PLANE_EVENT_META:
                continue
            value = next((v for k, v in _fields(buf, *entry) if k == 2), None)
            if value is None:
                continue
            names, stats = [], []
            for k, v in _fields(buf, *value):
                if k in (_META_NAME, _META_DISPLAY):
                    names.append(_text(buf, v))
                elif k == _META_STATS:
                    stat = dict(_fields(buf, *v))
                    key = stat_names.get(stat.get(_STAT_META_ID, 0), "")
                    if _STAT_STR in stat:
                        stats.append((key, _text(buf, stat[_STAT_STR])))
                    elif _STAT_REF in stat:
                        stats.append((key, stat_names.get(stat[_STAT_REF],
                                                          "")))
            found = scope_of(stats)
            if stats and (found[0] or not sample):
                sample = [[k, v[:300]] for k, v in stats]
            for n in names:
                if n:
                    scopes[n] = found
    return scopes, sample


def scope_of(stats) -> list:
    """``[scope, stat]``: the named scopes in the first string stat that
    holds any (the path components of an op name like
    ``jit(f)/while/body/hits.hub/scatter-add``), and that stat's name."""
    for key, value in stats:
        if isinstance(value, str):
            found = [p for p in re.split(r"[/ ]", value) if SCOPE.match(p)]
            if found:
                return ["/".join(found), key]
    return ["", ""]


def _union(intervals) -> list:
    out = []
    for s, e in sorted((float(s), float(e)) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def idle_in(rec: dict, intervals, chip: int = 0) -> float:
    """Nanoseconds of the window in which ``chip`` ran nothing, inside
    the union of ``intervals`` (``[start, end]`` on the trace's clock)."""
    iv = _union(intervals)
    total, j = 0.0, 0
    for g0, g1 in trace_reduce.idle_gaps(rec, chip):
        while j < len(iv) and iv[j][1] <= g0:
            j += 1
        k = j
        while k < len(iv) and iv[k][0] < g1:
            total += min(g1, iv[k][1]) - max(g0, iv[k][0])
            k += 1
    return total


def _owned(spans) -> list:
    """``[start, end, name]`` pieces of the time the spans cover, each
    owned by the shortest span that covers it: the innermost, where spans
    nest, and the most specific, where spans of other threads overlap."""
    pts = sorted({p for s in spans for p in (s[1], s[2])})
    by_start = sorted(spans, key=lambda s: s[1])
    active, out, i = [], [], 0
    for a, b in zip(pts, pts[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            s = by_start[i]
            heapq.heappush(active, (s[2] - s[1], i, s[2], s[0]))
            i += 1
        while active and active[0][2] <= a:
            heapq.heappop(active)
        if active:
            name = active[0][3]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1][1] = b
            else:
                out.append([a, b, name])
    return out


def _idle_pieces(rec: dict, spans, chip: int):
    """``(start, end, span name or None)`` of every stretch of the window
    in which ``chip`` ran nothing, each given to its innermost covering
    span (see ``_owned``), None where no span covers it."""
    pieces = _owned(spans)
    j = 0
    for g0, g1 in trace_reduce.idle_gaps(rec, chip):
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        cur, m = g0, j
        while m < len(pieces) and pieces[m][0] < g1:
            a, b, name = pieces[m]
            lo, hi = max(g0, a), min(g1, b)
            if lo > cur:
                yield cur, lo, None
            yield lo, hi, name
            cur = max(cur, hi)
            m += 1
        if g1 > cur:
            yield cur, g1, None


def idle_by_span(rec: dict, spans, k: int = 16, chip: int = 0) -> list:
    """``[[span name, seconds]]``: the chip's idle time split by the
    program span that covered it, innermost span first (see ``_owned``);
    idle time that no span covers is ``"no program span"``. Largest
    first, at most ``k``."""
    out: dict = {}
    for lo, hi, name in _idle_pieces(rec, spans, chip):
        out[name or NO_SPAN] = out.get(name or NO_SPAN, 0.0) + (hi - lo)
    return [[n, t * 1e-9] for n, t in
            sorted(out.items(), key=lambda kv: -kv[1])[:k]]


def uncovered(rec: dict, spans, k: int = 5, chip: int = 0) -> list:
    """``[[offset s, seconds]]`` of the ``k`` longest stretches of idle
    time that no span covers, the offset from the window's start."""
    lo = rec["window"][0]
    out = [[(a - lo) * 1e-9, (b - a) * 1e-9]
           for a, b, name in _idle_pieces(rec, spans, chip) if name is None]
    return sorted(out, key=lambda x: -x[1])[:k]


def in_window(rec: dict, spans) -> list:
    """The spans that end inside the traced window."""
    lo, hi = rec["window"]
    return [s for s in spans if lo <= s[2] <= hi]


def span_ms(spans) -> dict:
    """``{name: [count, mean ms]}``."""
    out: dict = {}
    for name, s, e, *_ in spans:
        c = out.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) * 1e-6
    return {n: [c, t / c] for n, (c, t) in sorted(out.items())}


def device_scopes(rec: dict, op_scopes: dict) -> list:
    """``[[scope, seconds]]``: leaf-op time in the window summed by the
    named scope its op carries (``"unscoped"`` for none), largest first."""
    lo, hi = rec["window"]
    out: dict = {}
    for _c, name, s, d in trace_reduce.leaf_ops(rec):
        t = min(s + d, hi) - max(s, lo)
        if t > 0:
            scope = op_scopes.get(name, ["", ""])[0] or "unscoped"
            out[scope] = out.get(scope, 0.0) + t
    return [[n, t * 1e-9] for n, t in
            sorted(out.items(), key=lambda kv: -kv[1])]
