"""One run of one cell: set-up, measured window, check, metrics.

Everything a cell is made of is found by name, so that a later change can
add a configuration, a traffic mix or a metric by adding files:

- ``BENCHMARK.json``, at the root of the checkout, names the cell's
  configuration and traffic mix; the files below are under that root;
- ``bench/configs/<config>.json`` holds the configuration: its graph, the
  entry point that serves it (``entry``), the settings it states, the
  lower-precision control and the limits its answers are held to;
- ``bench/traffic/<mix>.json`` holds the mix's parameters (``loadgen``);
- ``bench/drivers/<entry>.py`` drives an entry point: ``Driver(cfg, mix,
  seed, chips, control, log)`` with ``setup()``, ``window(seconds, win)``,
  ``release()`` and ``check()``;
- ``bench/metrics/<metric>.py`` reads one metric: ``read(run) -> number |
  None`` over the run's record, None where it finds nothing to read.

``run_cell`` does not look for a chip; ``bench/run.py`` does that first.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

def _load_module(path: Path, prefix: str):
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    name = prefix + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json (have "
                   f"{[c['name'] for c in bench['workloads']]})")


def load_config(root: Path, bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_mix(root: Path, name: str) -> dict:
    return json.loads((root / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def load_driver(root: Path, entry: str):
    return _load_module(root / "bench" / "drivers" / f"{entry}.py",
                        "bench_driver_")


def load_reader(root: Path, metric: str):
    return _load_module(root / "bench" / "metrics" / f"{metric}.py",
                        "bench_metric_")


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports: its end-to-end metrics in an
    untraced run, its per-layer metrics in a traced one."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


class Window:
    """The measured window's clock and, when tracing, the profiler around
    it. A driver calls ``begin()`` when its window starts (it returns the
    start on ``time.perf_counter``) and ``end()`` once the window's last
    answer is in."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        self.t0 = self.t1 = None
        self._mark = None

    def begin(self) -> float:
        if self.trace:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            from .trace_reduce import WINDOW_MARK
            self._mark = jax.profiler.TraceAnnotation(WINDOW_MARK)
            self._mark.__enter__()
        self.t0 = time.perf_counter()
        return self.t0

    def end(self) -> float:
        self.t1 = time.perf_counter()
        if self.trace:
            import jax
            self._mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return self.t1

    def record(self, host_spans) -> dict | None:
        """The reduced trace, with ``host_spans`` (label, t0, t1 on
        ``perf_counter``) moved onto the trace's clock; None untraced."""
        if not self.trace:
            return None
        from . import trace_reduce
        try:
            rec = trace_reduce.read_xplane(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        if rec["window"] is None:
            return None
        # the window mark opened just before t0: that fixes the offset
        off = rec["window"][0] - self.t0 * 1e9
        rec["host"] = [[lbl, s * 1e9 + off, e * 1e9 + off]
                       for lbl, s, e in host_spans]
        return rec


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    used = devs[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, control: bool = False, t_start: float = None,
             log=None) -> dict:
    """Run ``workload`` once and return its result line (a dict)."""
    from .compile_clock import CompileClock

    log = log or (lambda msg: print(msg, flush=True))
    t_start = time.perf_counter() if t_start is None else t_start
    bench = load_benchmark(root)
    cell = find_cell(bench, workload)
    cfg = load_config(root, bench, cell["config"])
    mix = load_mix(root, cell["traffic"])
    drv = load_driver(root, cfg["entry"]).Driver(cfg, mix, seed=seed,
                                           chips=cell["chips"],
                                           control=control, log=log)
    clock = CompileClock()
    drv.setup()
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f}s, {clock.since((0.0, 0, 0))}")
    win = Window(trace)
    mark = clock.mark()
    run = drv.window(float(seconds), win)
    in_window = clock.since(mark)
    log(f"window: {win.t1 - win.t0:.3f}s, inside it {in_window}")
    device = device_info(cell["chips"])
    run["trace"] = win.record(run.pop("host_spans", []))
    if run["trace"] is not None:
        from . import trace_reduce as tr
        device["busy_s"] = tr.busy_s(run["trace"])
        device["window_s"] = tr.window_s(run["trace"])
    drv.release()
    gc.collect()
    checks = drv.check(run)
    correct = all(v == v and v <= lim for v, lim in checks.values())

    from .peaks import peaks
    run.update(setup_s=setup_s, compiles_in_window=in_window,
               peaks=peaks(device["kind"]) if device["platform"] == "tpu"
               else None)
    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        value = load_reader(root, m["name"]).read(run)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": int(run["attempted"]),
           "failed": int(run["failed"]), "metrics": metrics,
           "device": device}
    if run["trace"] is not None and run["trace"]["device"]:
        from . import trace_reduce as tr
        out["breakdown"] = {"device_ops": tr.top_ops(run["trace"]),
                            "idle_gaps": tr.idle_by_host(run["trace"])}
    # a number that never came (inf, nan) is printed as null
    out["checks"] = {k: {"value": v if math.isfinite(v) else None,
                         "limit": lim} for k, (v, lim) in checks.items()}
    return out


def print_result(out: dict):
    """The compared numbers beside their limits as the last lines on
    stderr, then the result as the last line on stdout."""
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
