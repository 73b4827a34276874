#!/usr/bin/env python3
"""Run one benchmark cell traced and report the program's own spans.

  python3 bench/span_report.py --workload backbutton-stanford.rank \\
      --seed 7 --seconds 51

Runs the cell as ``bench/run.py --trace 1`` does, and prints its result
line, with the cell's end-to-end metrics beside its per-layer ones, so
that a traced run's cost shows against an untraced run's line. Before
the trace is dropped it also reads the program's spans and
its device ops' named scopes (``bench/spans.py``), and prints them as one
more JSON line, the last on stdout:

- ``idle_by_span``: the chip's idle time split by program span, innermost
  first, beside the result line's ``idle_gaps`` (by the benchmark's own
  host spans);
- ``uncovered``: the longest stretches of that idle time no span covers,
  as seconds from the window's start and seconds long;
- ``spans``: count and mean ms of each span ending in the window;
- ``idle_in_span_ms``: per span name, the chip's idle time inside its
  spans over their count;
- ``device_scopes``: leaf-op seconds by named scope, ``scope_stats``, the
  event stats that carried them, and ``op_stats``, every stat of one op.

The refusals are ``bench/run.py``'s.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT)]

from bench import run as bench_run  # noqa: E402  (its clock starts here)


def report(rec: dict) -> dict:
    from bench import spans as sp

    inside = sp.in_window(rec, rec["spans"])
    idle_in = {}
    for name in sorted({s[0] for s in inside}):
        mine = [s for s in inside if s[0] == name]
        idle_in[name] = 1e-6 * sp.idle_in(rec, [(s[1], s[2]) for s in mine]
                                          ) / len(mine)
    return {"idle_by_span": sp.idle_by_span(rec, rec["spans"]),
            "uncovered": sp.uncovered(rec, rec["spans"]),
            "spans": sp.span_ms(inside),
            "idle_in_span_ms": idle_in,
            "device_scopes": sp.device_scopes(rec, rec["op_scopes"]),
            "scope_stats": sorted({v[1] for v in rec["op_scopes"].values()
                                   if v[1]}),
            "op_stats": rec["op_stats"]}


def main(argv=None):
    from bench import harness, spans

    recs = []

    class SpanWindow(harness.Window):
        def record(self, host_spans):
            extra = None
            try:
                extra = spans.read(self.dir) if self.trace else None
            except Exception as e:  # noqa: BLE001 — the run's line still comes
                print(f"span_report: spans not read: {e!r}", file=sys.stderr)
            rec = super().record(host_spans)
            if rec is not None and extra is not None:
                rec.update(extra)
                recs.append(rec)
            return rec

    harness.Window = SpanWindow
    metrics_of = harness.cell_metrics
    harness.cell_metrics = lambda bench, cell, trace: (
        metrics_of(bench, cell, False) + metrics_of(bench, cell, trace))
    argv = list(sys.argv[1:] if argv is None else argv)
    bench_run.main(argv + ["--trace", "1"])
    if recs:
        print(json.dumps(report(recs[-1])), flush=True)


if __name__ == "__main__":
    main()
