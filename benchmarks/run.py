"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Default scale=0.25 of the
paper's Table 7 datasets keeps a full run a few minutes on CPU; pass
--full for scale=1.0 (the EXPERIMENTS.md numbers).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)  # fp64 ranking oracles


def _emit(name, seconds_per_call, derived):
    print(f"{name},{seconds_per_call*1e6:.1f},{derived}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale (1.0)")
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--names", default="wikipedia,jobs,opera,britannica")
    ap.add_argument("--json-out", default="results/bench")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    scale = args.scale or (1.0 if args.full else 0.25)
    names = args.names.split(",") if args.names != "all" else None
    os.makedirs(args.json_out, exist_ok=True)

    from . import paper_tables as pt

    print("name,us_per_call,derived")

    t0 = time.perf_counter()
    conv = pt.convergence(scale, names)
    t_conv = time.perf_counter() - t0
    for row in conv:
        _emit(f"fig2_3/convergence/{row['dataset']}/{row['variant']}",
              t_conv / len(conv),
              f"iters h={row['iters_hits']} a={row['iters_accel']} "
              f"p={row['iters_pagerank']}")
    accel_wins_bb = sum(1 for r in conv if r["variant"] == "backbutton"
                        and r["iters_accel"] <= min(r["iters_hits"],
                                                    r["iters_pagerank"]))
    n_bb = sum(1 for r in conv if r["variant"] == "backbutton")
    _emit("fig3/claim/accel_fastest_backbutton", 0,
          f"{accel_wins_bb}/{n_bb} datasets")

    tim = pt.timing(scale, names)
    for row in tim:
        _emit(f"fig2i_3i/timing/{row['dataset']}/{row['variant']}",
              row["time_accel_s"],
              f"speedup_vs_hits={row['time_hits_s']/max(row['time_accel_s'],1e-9):.2f}x "
              f"vs_pr={row['time_pagerank_s']/max(row['time_accel_s'],1e-9):.2f}x")

    t0 = time.perf_counter()
    deg = pt.degree_similarity(scale, names)
    dt = time.perf_counter() - t0
    for row in deg:
        _emit(f"table1/degree_similarity/{row['dataset']}", dt / len(deg),
              f"cosA={row['cos_auth_indeg']:.3f} spH={row['sp_hub_outdeg']:.3f}")

    for row in pt.costs(scale, names):
        _emit(f"table2_5/costs/{row['dataset']}", 0,
              f"N={row['N']} nnz={row['nnz']} prop_mult={row['prop_mult']} "
              f"prop_add={row['prop_add']}")

    fr = pt.fractions(scale, names)
    _emit("table6/fractions/orig", 0,
          f"fi>0.6={fr['orig']['fi>0.6']:.3f} fo>0.6={fr['orig']['fo>0.6']:.3f}")
    _emit("table6/fractions/backbutton", 0,
          f"fi>0.6={fr['backbutton']['fi>0.6']:.3f} "
          f"fo>0.6={fr['backbutton']['fo>0.6']:.3f}")

    t0 = time.perf_counter()
    sim = pt.similarity(scale, names)
    dt = time.perf_counter() - t0
    for row in sim:
        _emit(f"table8/similarity/{row['dataset']}/{row['variant']}",
              dt / len(sim),
              f"cosA={row['cos_auth']:.3f} cosH={row['cos_hub']:.3f} "
              f"spA={row['sp_auth']:.3f}")

    tp = pt.toppages(scale, names[0] if names else "wikipedia")
    _emit("table9_10/toppages", 0,
          f"overlap_accel_hits={tp['overlap_accel_hits']:.2f}")

    # kernel microbench: BSR Pallas path vs segment-sum reference (CPU
    # interpret mode — correctness-path timing, TPU is the perf target)
    import jax
    import jax.numpy as jnp
    from repro.core import accel_weights
    from repro.core.hits import EdgeList, hits_sweep
    from repro.graph import paper_dataset
    from repro.kernels import hits_sweep_bsr

    g = paper_dataset("wikipedia", scale=min(scale, 0.25))
    ca, ch = accel_weights(g.indeg(), g.outdeg())
    sweep_k, _, _ = hits_sweep_bsr(g, ca, ch, bs=128)
    h = jnp.full((g.n_nodes,), 1.0 / g.n_nodes, jnp.float32)
    sweep_k(h)
    t0 = time.perf_counter()
    for _ in range(3):
        hk, _ = sweep_k(h)
    _emit("kernel/bsr_sweep_interpret", (time.perf_counter() - t0) / 3,
          f"n={g.n_nodes} e={g.n_edges}")
    sweep_r = jax.jit(hits_sweep(EdgeList.from_graph(g),
                                 ca=jnp.asarray(ca, jnp.float32),
                                 ch=jnp.asarray(ch, jnp.float32)))
    sweep_r(h)
    t0 = time.perf_counter()
    for _ in range(10):
        hr, _ = sweep_r(h)
    _emit("kernel/segment_sum_sweep", (time.perf_counter() - t0) / 10,
          f"kernel_vs_ref_err={float(jnp.abs(hk - hr).max()):.2e}")

    # persist machine-readable results
    out = {"scale": scale, "convergence": [
        {k: (v.tolist() if isinstance(v, np.ndarray) else v)
         for k, v in row.items()} for row in conv],
        "timing": tim, "similarity": sim, "degree": deg,
        "fractions": fr, "toppages": tp}
    with open(os.path.join(args.json_out, f"paper_scale{scale}.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
