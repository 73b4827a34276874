#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

  python3 bench/run.py --workload kleinberg-stanford.overload --seed 7 \\
      --seconds 20 --trace 0

The cells, their configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout (see ``bench/harness.py``
for where each is found). The last line on stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with a
traced run's ``breakdown``, and last ``checks``: each number held to the
reference beside its limit, which also end stderr.

``--control`` puts the configuration's lower-precision control in the
program's place; its run must come out not correct.

The script refuses to run (non-zero exit, no result line) off a TPU, with
fewer chips than the cell asks for, with ``REPRO_PALLAS_INTERPRET`` set, or
without the program beside it. JAX's persistent compilation cache is kept
at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def refuse(why: str):
    print(f"bench: refusing to run: {why}", file=sys.stderr, flush=True)
    sys.exit(2)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the lower-precision control in the program's "
                         "place")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_PALLAS_INTERPRET"):
        refuse("REPRO_PALLAS_INTERPRET is set; the chip path is compiled")
    if not (ROOT / "src" / "repro").is_dir():
        refuse(f"no program beside the benchmark ({ROOT / 'src' / 'repro'})")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        refuse(f"no workload {args.workload!r} (have {sorted(cells)})")
    chips = int(cells[args.workload]["chips"])

    # fixed, inside the checkout: the path is part of the cache's key
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_enable_x64", True)  # as every entry point has it
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        refuse(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        refuse(f"{args.workload} needs {chips} chips; JAX sees "
               f"{len(devices)}")
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}", flush=True)

    from bench import harness
    out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                           bool(args.trace), control=args.control,
                           t_start=T_START)
    harness.print_result(out)


if __name__ == "__main__":
    main()
